"""Family `resnet`: bottleneck ResNets (He et al. 2015, arXiv:1512.03385,
Table 1) through the program's `zoo.ResNet50` -> `ComputationGraph`.

The program side is `build` (the zoo model as a user builds it, with the
on-device image scaler installed) and the small adapters the drivers call;
the yardstick side is `flops_per_item` (from shapes) and `reference_forward`
(plain `jax.numpy`, float32, highest matmul precision, no kernels), which
reads the system's own parameter pytree and follows the paper, not the
program's code.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def _stages(config: dict) -> Tuple[Tuple[int, int], ...]:
    return tuple((int(n), int(ch)) for n, ch in config["stages"])


def conv_table(config: dict) -> List[dict]:
    """Every convolution of the network: name, kernel, stride, channels in
    and out, output height and width ('Same' padding: ceil(h / stride))."""
    h, w, c = config["input_shape"]
    out = []

    def conv(name, k, s, cin, cout, h, w):
        oh, ow = -(-h // s), -(-w // s)
        out.append(dict(name=name, k=k, stride=s, cin=cin, cout=cout,
                        oh=oh, ow=ow))
        return oh, ow

    h, w = conv("stem", 7, 2, c, 64, h, w)
    h, w = -(-h // 2), -(-w // 2)                      # 3x3/2 max pool
    cin = 64
    for si, (blocks, ch) in enumerate(_stages(config)):
        for bi in range(blocks):
            s = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            if bi == 0:
                conv(f"{name}_proj", 1, s, cin, ch * 4, h, w)
            h, w = conv(f"{name}_a", 1, s, cin, ch, h, w)
            conv(f"{name}_b", 3, 1, ch, ch, h, w)
            conv(f"{name}_c", 1, 1, ch, ch * 4, h, w)
            cin = ch * 4
    return out


def conv_flops(c: dict) -> float:
    """Multiply-adds x 2 of one convolution's forward pass, one image."""
    return 2.0 * c["oh"] * c["ow"] * c["k"] * c["k"] * c["cin"] * c["cout"]


def forward_flops(config: dict) -> float:
    convs = conv_table(config)
    dense = 2.0 * convs[-1]["cout"] * config["n_classes"]
    return sum(conv_flops(c) for c in convs) + dense


def flops_per_item(config: dict, traffic: dict, training: bool = True) -> float:
    """FLOPs one image requires: the forward pass, and for training the two
    backward products of every convolution and of the classifier (3x the
    forward) less the stem's input gradient, which nothing needs.
    Elementwise work (batch norm, relu, pooling, the updater) is not counted:
    the roofline it is set against is the MXU's."""
    fwd = forward_flops(config)
    if not training:
        return fwd
    return 3.0 * fwd - conv_flops(conv_table(config)[0])


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def build(config: dict, seed: int, serving: bool = False):
    """`zoo.ResNet50(...).init_model()` with the file's sizes and f32 master
    parameters initialised on the device from `seed`.  For training, whose
    images are uint8, the [0, 255] -> [0, 1] scaler is folded into the
    compiled step (`set_normalizer`); a serving client sends scaled rows."""
    from deeplearning4j_tpu.data.normalizers import ImagePreProcessingScaler
    from deeplearning4j_tpu.train import updaters
    from deeplearning4j_tpu.zoo import ResNet50

    u = config["updater"]
    zoo_cls = ResNet50
    if _stages(config) != tuple(ResNet50.STAGES):
        zoo_cls = type("ResNetCut", (ResNet50,), {"STAGES": _stages(config)})
    net = zoo_cls(n_classes=int(config["n_classes"]),
                  input_shape=tuple(config["input_shape"]),
                  seed=int(seed),
                  updater=getattr(updaters, u["kind"])(*u["args"]),
                  compute_dtype=config["compute_dtype"]).init_model()
    if not serving:
        net.set_normalizer(ImagePreProcessingScaler())
    return net


def make_pool(config: dict, traffic: dict, seed: int, rows: int):
    """`pool_batches` host batches of `rows` uint8 images and one-hot
    labels, from the seed.  An image is a flat colour of its own (each
    channel uniform in [32, 224)) plus uniform noise in [-32, 32]: images
    that differ from one another as photographs do, in brightness and hue.
    Pure noise images are all alike to a batch norm, and what is left after
    it subtracts their common mean is mostly rounding (PERF.md, PR 22)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(seed)
    h, w, c = config["input_shape"]
    n = int(config["n_classes"])
    pool = []
    for _ in range(int(traffic["pool_batches"])):
        x = rng.integers(-32, 33, (rows, h, w, c), dtype=np.int16)
        x += rng.integers(32, 224, (rows, 1, 1, c), dtype=np.int16)
        x = x.astype(np.uint8)
        y = np.zeros((rows, n), np.float32)
        y[np.arange(rows), rng.integers(0, n, rows)] = 1.0
        pool.append(DataSet(x, y))
    return pool


def items_per_row(config: dict, traffic: dict) -> dict:
    return {"samples": 1}


def step_hook(model, hook) -> bool:
    """Have `hook()` called after every optimizer step through the program's
    listener interface.  True: attached."""
    class _Listener:
        def iteration_done(self, model, iteration, epoch):
            hook()
    model.listeners.append(_Listener())
    return True


def last_loss(model):
    """The newest minibatch loss as a device scalar; no host sync."""
    return model.score_array()


def parameters(model):
    return model.params_


def _cross_entropy(probs, onehot) -> float:
    p = np.asarray(probs, np.float64)
    y = np.asarray(onehot, np.float64)
    return float(-np.mean(np.sum(y * np.log(np.maximum(p, 1e-30)), -1)))


def eval_loss(model, batch, rows: int) -> float:
    """Cross-entropy of the system's output on the batch's first `rows`,
    with batch statistics in the norm layers (`output(train=True)`): the
    running statistics of a fresh net are 0 and 1, which no forward pass
    survives, so only this mode can be set before and after training."""
    (probs,) = model.output(batch.features[:rows], train=True)
    return _cross_entropy(probs, batch.labels[:rows])


def _centered_logits(probs) -> np.ndarray:
    """Logits up to their row mean, from probabilities: what `output` gives
    is a softmax, and rounding is judged on what went into it."""
    z = np.log(np.maximum(np.asarray(probs, np.float64), 1e-38))
    return z - z.mean(-1, keepdims=True)


def reference_check(model, config: dict, batch, rows: int) -> dict:
    """The system's output on `rows` images against `reference_forward` on
    the same parameters, both with batch statistics in the norm layers
    (`output(train=True)`): the forward pass of training.  The error is the
    root-mean-square difference of the centered logits over their own
    root-mean-square (the largest single difference is several times that
    and varies with the seed).  Inference mode is
    not compared here: its running statistics start at 0 and 1 and, after a
    window's worth of steps on noise, still leave activations at scales
    where the comparison measures rounding luck (PERF.md, PR 22)."""
    import jax
    x = batch.features[:rows]
    (got,) = model.output(x, train=True)
    want = jax.jit(lambda p, xv: reference_forward(
        config, p, None, xv))(model.params_, x)
    zg, zw = _centered_logits(got), _centered_logits(want)
    err = float(np.sqrt(np.mean((zg - zw) ** 2) / np.mean(zw ** 2)))
    return {"rel_err": err, "tol": float(config["tolerance"]["output_rel"]),
            "loss": _cross_entropy(got, batch.labels[:rows]),
            "loss_reference": _cross_entropy(want, batch.labels[:rows]),
            "loss_tol": float(config["tolerance"]["loss_rel"])}


def serve_rows(config: dict, seed: int, n: int) -> np.ndarray:
    """`n` float32 images in [0, 1], as a serving client sends them."""
    rng = np.random.default_rng(seed)
    return rng.random((n,) + tuple(config["input_shape"]), dtype=np.float32)


def serve_direct(model, x) -> np.ndarray:
    """The system's own forward outside the server (`entry.model.output`)."""
    (out,) = model.output(x)
    return np.asarray(out, np.float32)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def reference_forward(config: dict, params, state, x, scaled: bool = False):
    """Class probabilities of a bottleneck ResNet, v1 as in the paper
    (stride on the first 1x1 of a block): float32, highest matmul precision.
    With `state` (the running statistics) the norm layers are in inference
    mode; with `state=None` they use the batch's own statistics, as the
    forward pass of training does.

    Departures from the paper, all the program's: batch norm has eps 1e-5
    and no bias in the convolutions; the shortcut of each stage's first
    block is a strided 1x1 projection (the paper's option B).
    `x` is uint8 [0, 255] (scaled here to [0, 1]) unless `scaled`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32

    def conv(name, v, stride):
        return lax.conv_general_dilated(
            v, params[f"{name}_conv"]["W"].astype(f32), (stride, stride),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def bn(name, v):
        p = params[f"{name}_bn"]
        if state is None:
            mean = jnp.mean(v, (0, 1, 2))
            var = jnp.mean((v - mean) ** 2, (0, 1, 2))
        else:
            mean = state[f"{name}_bn"]["mean"].astype(f32)
            var = state[f"{name}_bn"]["var"].astype(f32)
        v = (v - mean) / jnp.sqrt(var + 1e-5)
        return v * p["gamma"].astype(f32) + p["beta"].astype(f32)

    with jax.default_matmul_precision("highest"):
        v = jnp.asarray(x).astype(f32)
        if not scaled:
            v = v / f32(255.0)
        v = jax.nn.relu(bn("stem", conv("stem", v, 2)))
        v = lax.reduce_window(v, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        for si, (blocks, _) in enumerate(_stages(config)):
            for bi in range(blocks):
                s = 2 if (bi == 0 and si > 0) else 1
                n = f"s{si}b{bi}"
                short = bn(f"{n}_proj", conv(f"{n}_proj", v, s)) \
                    if bi == 0 else v
                y = jax.nn.relu(bn(f"{n}_a", conv(f"{n}_a", v, s)))
                y = jax.nn.relu(bn(f"{n}_b", conv(f"{n}_b", y, 1)))
                y = bn(f"{n}_c", conv(f"{n}_c", y, 1))
                v = jax.nn.relu(y + short)
        v = jnp.mean(v, axis=(1, 2))
        out = params["output"]
        logits = v @ out["W"].astype(f32) + out["b"].astype(f32)
        return jax.nn.softmax(logits, -1)
