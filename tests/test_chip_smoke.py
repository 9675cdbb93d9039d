"""`chip_smoke.py` off the chip: it says which platform it found and
exits non-zero — no CPU branch, no result line.  (What it does on
the chip is the driver's chip check; see README "Running".)"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_needs_the_chip_and_names_what_it_found(script):
    p = subprocess.run([sys.executable, os.path.join(REPO, script)],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "platform='cpu'" in p.stderr
    assert '"ok"' not in p.stdout and '"metric"' not in p.stdout


_NO_FILE = """
import resource, sys
resource.setrlimit(resource.RLIMIT_FSIZE, (0, 0))    # any file write: EFBIG
sys.path.insert(0, {repo!r})
import numpy as np
import chip_smoke
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.zoo import BertConfig, LeNet
chip_smoke.phase_bert(BertConfig.tiny(max_len=16, compute_dtype="bfloat16"),
                      batch=4)
net = LeNet(n_classes=10, input_shape=(28, 28, 1)).init_model()
loaded = MultiLayerNetwork.load(chip_smoke._saved(net))
chip_smoke._same_leaves("lenet", net.params_, loaded.params_)
print("NO-FILE-OK")
"""


def test_save_load_round_trip_writes_no_file():
    """The chip machine may cap file sizes (the driver's refused BERT-base's
    1.3 GB zip with EFBIG): the smoke's save -> load goes through memory,
    for both serializers."""
    p = subprocess.run([sys.executable, "-c", _NO_FILE.format(repo=REPO)],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "NO-FILE-OK" in p.stdout
