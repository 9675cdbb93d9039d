"""Character-level LSTM language model + sampling (reference
dl4j-examples `LSTMCharModellingExample.java` — GravesLSTM char-LM)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np

from deeplearning4j_tpu.zoo import TextGenLSTM

CORPUS = (
    "the quick brown fox jumps over the lazy dog. "
    "pack my box with five dozen liquor jugs. "
    "how vexingly quick daft zebras jump! "
) * 8
SEQ_LEN = 32


def main():
    chars = sorted(set(CORPUS))
    idx = {c: i for i, c in enumerate(chars)}
    v = len(chars)
    enc = np.asarray([idx[c] for c in CORPUS], np.int32)

    # one-hot windows, next-char targets
    starts = np.arange(0, len(enc) - SEQ_LEN - 1, SEQ_LEN // 2)
    xs = np.stack([enc[s:s + SEQ_LEN] for s in starts])
    ys = np.stack([enc[s + 1:s + SEQ_LEN + 1] for s in starts])
    x = np.eye(v, dtype=np.float32)[xs]
    y = np.eye(v, dtype=np.float32)[ys]

    from deeplearning4j_tpu.train.updaters import Adam
    net = TextGenLSTM(n_classes=v, input_shape=(SEQ_LEN, v),
                      lstm_units=96, updater=Adam(5e-3)).init_model()
    for epoch in range(120):
        net.fit(x, y)
    print(f"final loss: {net.score():.3f}")

    # greedy generation from a seed
    seed = "the quick "
    state = [idx[c] for c in seed]
    rng = np.random.RandomState(0)
    for _ in range(60):
        window = state[-SEQ_LEN:]
        inp = np.eye(v, dtype=np.float32)[np.asarray(window)][None]
        probs = np.asarray(net.output(inp))[0, len(window) - 1]
        p = probs / probs.sum()
        state.append(int(rng.choice(v, p=p)))
    print("sample:", "".join(chars[i] for i in state))


if __name__ == "__main__":
    main()
