"""The layer-wise trainer (nn/trainer.py): the one per-layer update loop
against a reference written out here in plain jnp, and the two models that
share it against each other.

The loop is called by the fused step and by the apply half of the split
step of `MultiLayerNetwork` and `ComputationGraph`; each case below is a
branch of it that one of the four former copies had."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import (ComputationGraph, DenseLayer, GraphBuilder,
                                   InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.nn.trainer import apply_layer_updates
from deeplearning4j_tpu.parallel import make_mesh, zero
from deeplearning4j_tpu.parallel.hierarchical import (
    HierarchicalGradientSharing)
from deeplearning4j_tpu.train.updaters import UPDATERS, Adam

LR, B1, B2, EPS = 1e-2, 0.9, 0.999, 1e-8
ADAM = Adam(LR, B1, B2, EPS)


def _conf(gn=None, thr=1.0, wd=0.0):
    return types.SimpleNamespace(gradient_normalization=gn,
                                 gradient_normalization_threshold=thr,
                                 weight_decay=wd)


def _tree(seed, shapes=((10, 6), (6,))):
    rng = np.random.RandomState(seed)
    return {"W": jnp.asarray(rng.randn(*shapes[0]), jnp.float32),
            "b": jnp.asarray(rng.randn(*shapes[1]), jnp.float32)}


def _world(entries, seed=0):
    """(params, opt_state, grads) for `entries`; moments start non-zero so
    a pass-through is told from an update."""
    params, opt, grads = {}, {}, {}
    for i, (name, _, _) in enumerate(entries):
        params[name] = _tree(seed + 10 * i)
        grads[name] = _tree(seed + 10 * i + 1)
        opt[name] = {"m": _tree(seed + 10 * i + 2),
                     "v": jax.tree_util.tree_map(jnp.abs,
                                                 _tree(seed + 10 * i + 3))}
    return params, opt, grads


def _reference(entries, conf, params, opt, grads, it):
    """Adam under the loop's semantics, formula by formula."""
    new_p, new_o = {}, {}
    t = np.float32(it) + np.float32(1.0)
    alpha = LR * jnp.sqrt(1.0 - B2 ** t) / (1.0 - B1 ** t)
    for name, layer, _ in entries:
        if not params[name] or (layer is not None and layer.frozen):
            new_p[name], new_o[name] = params[name], opt[name]
            continue
        own = layer is not None and layer.gradient_normalization is not None
        mode = layer.gradient_normalization if own \
            else conf.gradient_normalization
        thr = layer.gradient_normalization_threshold if own \
            else conf.gradient_normalization_threshold
        g = dict(grads[name])
        if mode == "ClipL2PerLayer":
            norm = jnp.sqrt(jnp.sum(g["W"] * g["W"]) + jnp.sum(g["b"] * g["b"]))
            g = {k: v * jnp.minimum(1.0, thr / jnp.maximum(norm, 1e-12))
                 for k, v in g.items()}
        elif mode == "ClipElementWiseAbsoluteValue":
            g = {k: jnp.clip(v, -thr, thr) for k, v in g.items()}
        else:
            assert mode is None
        wd = 0.0
        if layer is not None:
            wd = layer.weight_decay if layer.weight_decay is not None \
                else conf.weight_decay
        new_p[name], new_o[name] = {}, {"m": {}, "v": {}}
        for k in ("W", "b"):
            m = B1 * opt[name]["m"][k] + (1 - B1) * g[k]
            v = B2 * opt[name]["v"][k] + (1 - B2) * g[k] * g[k]
            u = alpha * m / (jnp.sqrt(v) + EPS)
            if wd and k == "W":          # biases are not regularizable
                u = u + LR * wd * params[name][k]
            new_p[name][k] = params[name][k] - u
            new_o[name]["m"][k], new_o[name]["v"][k] = m, v
    return new_p, new_o


def _assert_trees_close(got, want, exact=False):
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        if exact:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-7)


CASES = {
    # a frozen entry among live ones: params AND moments come back as given
    "frozen": (lambda: [("a", DenseLayer(n_out=6, frozen=True), ADAM),
                        ("b", DenseLayer(n_out=6), ADAM)],
               _conf(gn="ClipElementWiseAbsoluteValue", thr=0.3, wd=0.02)),
    # a graph vertex that is not a layer: the configuration's normalization
    # applies to it, its weight decay does not
    "no_layer": (lambda: [("v", None, ADAM), ("b", DenseLayer(n_out=6), ADAM)],
                 _conf(gn="ClipL2PerLayer", thr=0.5, wd=0.05)),
    # the layer's own normalization beats the configuration's, threshold too
    "layer_gradnorm_override": (
        lambda: [("a", DenseLayer(n_out=6,
                                  gradient_normalization="ClipL2PerLayer",
                                  gradient_normalization_threshold=0.25),
                  ADAM),
                 ("b", DenseLayer(n_out=6), ADAM)],
        _conf(gn="ClipElementWiseAbsoluteValue", thr=0.1)),
    # decoupled weight decay: W shrinks, b does not; a layer's own
    # coefficient (0.0 here: none) beats the configuration's
    "weight_decay_mask": (
        lambda: [("a", DenseLayer(n_out=6), ADAM),
                 ("b", DenseLayer(n_out=6, weight_decay=0.0), ADAM),
                 ("c", DenseLayer(n_out=6, weight_decay=0.3), ADAM)],
        _conf(wd=0.1)),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("skip_empty", [False, True], ids=["mln", "cg"])
def test_update_loop_matches_plain_reference(case, skip_empty):
    make, conf = CASES[case]
    entries = make()
    params, opt, grads = _world(entries)
    it = jnp.asarray(4, jnp.int32)
    got_p, got_o = apply_layer_updates(entries, conf, params, opt, grads, it,
                                       jnp.asarray(0, jnp.int32),
                                       skip_empty=skip_empty)
    want_p, want_o = _reference(entries, conf, params, opt, grads, 4)
    _assert_trees_close(got_p, want_p)
    _assert_trees_close(got_o, want_o)
    for name, layer, _ in entries:
        if layer is not None and layer.frozen:
            assert got_p[name] is params[name] and got_o[name] is opt[name]
    if case == "weight_decay_mask":
        # the reference above is not the only witness: b of every entry
        # moved exactly as without decay, W only where the coefficient is 0
        plain_p, _ = apply_layer_updates(entries, _conf(), params, opt, grads,
                                         it, 0, skip_empty=skip_empty)
        for name in "abc":
            _assert_trees_close(got_p[name]["b"], plain_p[name]["b"],
                                exact=True)
        _assert_trees_close(got_p["b"]["W"], plain_p["b"]["W"], exact=True)
        assert not np.array_equal(got_p["a"]["W"], plain_p["a"]["W"])
    if case == "no_layer":
        nodecay_p, _ = apply_layer_updates(
            entries, _conf(gn="ClipL2PerLayer", thr=0.5), params, opt, grads,
            it, 0, skip_empty=skip_empty)
        _assert_trees_close(got_p["v"], nodecay_p["v"], exact=True)


@pytest.mark.parametrize("updater", sorted(UPDATERS))
def test_empty_parameter_tree_is_a_pass_through_either_way(updater):
    """`ComputationGraph` skips an entry whose parameter tree is empty,
    `MultiLayerNetwork` runs it through its updater: no updater keeps state
    for a tree without leaves, so both give the same (empty) result, and
    the neighbours are not disturbed."""
    upd = UPDATERS[updater]()
    live = ("b", DenseLayer(n_out=6), ADAM)
    entries = [("pool", DenseLayer(n_out=6), upd), live]
    params, opt, grads = _world([live])
    params["pool"], grads["pool"] = {}, {}
    opt["pool"] = upd.init_state({})
    assert not jax.tree_util.tree_leaves(opt["pool"])
    conf = _conf(gn="ClipL2PerLayer", thr=0.5, wd=0.05)
    outs = [apply_layer_updates(entries, conf, params, opt, grads,
                                jnp.asarray(2, jnp.int32), 0,
                                skip_empty=skip) for skip in (False, True)]
    for new_p, new_o in outs:
        assert new_p["pool"] == {}
        assert (jax.tree_util.tree_structure(new_o["pool"])
                == jax.tree_util.tree_structure(opt["pool"]))
    _assert_trees_close(outs[0][0]["b"], outs[1][0]["b"], exact=True)
    _assert_trees_close(outs[0][1]["b"], outs[1][1]["b"], exact=True)


def test_zero1_update_layout_or_not_gives_the_replicated_update():
    """Under a ZeRO-1 transform the fused step hands the loop gradients in
    their natural layout (normalize, then scatter) and the apply half hands
    it gradients that are padded and sharded already (`constrain_update`,
    then normalize): both are the update a replicated loop computes.  The
    10-row leaves do not divide by 4, so the padded path is the one run."""
    mesh = make_mesh({"data": 4}, jax.devices()[:4])
    entries = [("a", DenseLayer(n_out=6,
                                gradient_normalization="ClipL2PerLayer",
                                gradient_normalization_threshold=0.5), ADAM),
               ("f", DenseLayer(n_out=6, frozen=True), ADAM),
               ("b", DenseLayer(n_out=6), ADAM)]
    conf = _conf(gn="ClipElementWiseAbsoluteValue", thr=0.3, wd=0.05)
    params, opt, grads = _world(entries)
    it = jnp.asarray(3, jnp.int32)
    want_p, want_o = apply_layer_updates(entries, conf, params, opt, grads,
                                         it, 0)
    plans = zero.build_plans(params, mesh)
    assert plans["a"]["W"].pad == 2
    zt = zero.Zero1Transform(mesh, "data", plans)
    z_params = zero._place_params(params, plans, mesh)
    z_opt = zero._place_opt_state(opt, plans, mesh)

    def fused(p, o, g):
        return apply_layer_updates(entries, conf, p, o, g, it, 0, zt)

    def split(p, o, g):
        wire = {n: zt.scatter(n, g[n]) for n, _, _ in entries}
        return apply_layer_updates(entries, conf, p, o, wire, it, 0, zt,
                                   grads_in_update_layout=True)

    with mesh:
        outs = [jax.jit(f)(z_params, z_opt, grads) for f in (fused, split)]
    unpad = lambda tree: jax.tree_util.tree_map(
        lambda a, ref: np.asarray(a)[: ref.shape[0]], tree, want_o)
    for new_p, new_o in outs:
        _assert_trees_close(new_p, want_p)
        _assert_trees_close(unpad(new_o), want_o)
    _assert_trees_close(outs[0][0], outs[1][0], exact=True)


# ---------------------------------------------------------------------------
# one trainer, two models
# ---------------------------------------------------------------------------

def _layers():
    return [DenseLayer(name="a", n_out=16, activation="relu"),
            DenseLayer(name="b", n_out=12, activation="tanh",
                       gradient_normalization="ClipL2PerLayer",
                       gradient_normalization_threshold=0.5,
                       weight_decay=0.05),
            OutputLayer(name="out", n_out=3, activation="softmax",
                        loss="mcxent")]


def _as_mln():
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-2))
            .weight_decay(0.01)
            .gradient_normalization("ClipElementWiseAbsoluteValue", 1.0)
            .list(_layers()).set_input_type(InputType.feed_forward(10))
            .build())
    return MultiLayerNetwork(conf).init()


def _as_cg():
    a, b, out = _layers()
    conf = (GraphBuilder().seed(7).updater(Adam(1e-2)).weight_decay(0.01)
            .gradient_normalization("ClipElementWiseAbsoluteValue", 1.0)
            .add_inputs("in").set_input_types(InputType.feed_forward(10))
            .add_layer("a", a, "in").add_layer("b", b, "a")
            .add_layer("out", out, "b").set_outputs("out").build())
    return ComputationGraph(conf).init()


@pytest.mark.parametrize("path", ["fused", "split", "scan"])
def test_mln_and_cg_take_bit_equal_steps(path):
    """The same 3-layer net written as a stack and as a graph goes through
    the one trainer: 3 steps leave bit-equal parameters and moments."""
    rng = np.random.RandomState(0)
    xs = rng.randn(3, 8, 10).astype(np.float32)
    ys = np.eye(3, dtype=np.float32)[rng.randint(0, 3, (3, 8))]
    mln, cg = _as_mln(), _as_cg()
    _assert_trees_close(mln.params_, cg.params_, exact=True)
    if path == "split":
        for net in (mln, cg):
            net.set_gradient_sharing(HierarchicalGradientSharing(
                compressed=False, world=1))
    if path == "scan":
        l_mln = mln.fit_steps(xs, ys)
        l_cg = cg.fit_steps(xs, [ys])
        np.testing.assert_array_equal(np.asarray(l_mln), np.asarray(l_cg))
    else:
        for x, y in zip(xs, ys):
            mln.fit(x, y)
            cg.fit(x, y)
    assert mln.iteration == cg.iteration == 3
    assert mln._last_batch_size == cg._last_batch_size == 8
    assert mln.score() == cg.score()
    _assert_trees_close(mln.params_, cg.params_, exact=True)
    _assert_trees_close(mln.opt_state_, cg.opt_state_, exact=True)
    for net in (mln, cg):
        net.set_gradient_sharing(None)
