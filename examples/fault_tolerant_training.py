"""Fault-tolerant training — checkpoints, auto-resume, divergence guard.

A production run dies mid-training (preemption, OOM, plain crash) and is
relaunched with the same command line; the relaunch must pick up where
the dead process stopped and finish with *bitwise-identical* parameters
to a run that was never interrupted.  This example stages that whole
story in one process (docs/robustness.md):

1. train a reference net uninterrupted;
2. train the same net under `FaultTolerantTrainer` with an async
   `CheckpointManager`, and let a `chaos.KillSwitch` hook crash the run
   partway;
3. "relaunch": rebuild the net from scratch, point a fresh trainer at
   the same checkpoint directory, train again — it auto-resumes from the
   newest intact checkpoint, fast-forwards the iterator, and the final
   parameters match the reference bit for bit;
4. re-run with a poisoned (exploding) batch in the stream and a
   `DivergenceGuard` that skips the bad update instead of letting one
   rotten batch destroy the run.
"""
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import shutil
import tempfile

import numpy as np

from deeplearning4j_tpu.data import ArrayDataSetIterator
from deeplearning4j_tpu.data.normalizers import NormalizerStandardize
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.train import Adam
from deeplearning4j_tpu.train.resilience import (CheckpointManager,
                                                 DivergenceGuard,
                                                 FaultTolerantTrainer)
from deeplearning4j_tpu.utils import chaos

rng = np.random.default_rng(0)
X = rng.standard_normal((96, 16)).astype(np.float32)
Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 96)]
EPOCHS = 4                              # batch 8 -> 12 steps/epoch, 48 total


def make_net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list([DenseLayer(n_out=32, activation="tanh"),
                   OutputLayer(n_out=4, loss="mcxent", activation="softmax")])
            .set_input_type(InputType.feed_forward(16)).build())
    return MultiLayerNetwork(conf).init()


def data():
    return ArrayDataSetIterator(X, Y, 8)


def main():
    work = tempfile.mkdtemp(prefix="ft_example_")
    try:
        # ---- 1. the uninterrupted reference ----------------------------
        norm = NormalizerStandardize().fit(data())
        ref = make_net()
        FaultTolerantTrainer(ref, normalizer=norm).fit(data(), epochs=EPOCHS)
        print(f"reference: {ref.iteration} steps, "
              f"score {float(ref.score()):.6f}")

        # ---- 2. the run that dies --------------------------------------
        ckpt_dir = os.path.join(work, "ckpt")
        net = make_net()
        mgr = CheckpointManager(ckpt_dir, keep_last=3, save_every_steps=5,
                                async_save=True)
        boom = chaos.KillSwitch(at_step=30, mode="exception",
                                marker=os.path.join(work, "killed_once"))
        try:
            FaultTolerantTrainer(net, mgr, normalizer=norm,
                                 hooks=[boom]).fit(data(), epochs=EPOCHS)
        except chaos.ChaosError:
            print(f"crashed at step {net.iteration} "
                  f"(newest checkpoint: step {mgr.latest_step()})")

        # ---- 3. the "relaunch" -----------------------------------------
        # Fresh process in real life: nothing survives but the checkpoint
        # directory.  No normalizer is passed in — the trainer rebuilds it
        # from checkpoint metadata.
        net = make_net()
        mgr = CheckpointManager(ckpt_dir, keep_last=3, save_every_steps=5,
                                async_save=True)
        trainer = FaultTolerantTrainer(net, mgr)
        trainer.fit(data(), epochs=EPOCHS)
        print(f"resumed from step {trainer.resumed_from['step']}, "
              f"finished at {net.iteration}")
        bitwise = np.array_equal(np.asarray(ref.params()),
                                 np.asarray(net.params()))
        print(f"bitwise match with uninterrupted run: {bitwise}")
        assert bitwise, "auto-resume must be invisible to the math"

        # ---- 4. divergence guard ---------------------------------------
        Xbad = X.copy()
        Xbad[40:48] = np.nan            # batch 5 is corrupt: NaN loss
        guarded = make_net()
        guard = DivergenceGuard(policy="skip", max_score=50.0)
        FaultTolerantTrainer(guarded, normalizer=norm, divergence=guard).fit(
            ArrayDataSetIterator(Xbad, Y, 8), epochs=EPOCHS)
        print(f"guard skipped {guard.events} poisoned update(s); final "
              f"score {float(guarded.score()):.6f} stayed finite")
        assert np.isfinite(float(guarded.score()))
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
