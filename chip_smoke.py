"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process takes the TPU itself and drives the main path once through the
public entry points, at the full published width and depth of two zoo
models, on synthetic data made from a seed:

  resnet50  ResNet-50 224x224x3, 1000 classes, batch 64, bf16 compute,
            Nesterovs: `fit` steps + one `fit_steps` block, falling finite
            loss, then `output`/`score` on the trained net, `save` -> `load`
            (through memory: no large file) with equal parameters, and a
            step on the loaded net
  bert      BERT-base 12x768, seq 128, batch 64, bf16 compute, Adam: the
            same sequence through `fit_batch`/`fit_steps`/`output_mlm`, and
            a check that the lowered step really holds the Mosaic calls the
            dispatcher chose
  serve     `ModelServer.deploy(zoo="ResNet50", warmup=True)` on a short
            bucket ladder, concurrent `output(..., deadline_ms=...)` calls
            from threads, replies equal to the direct forward, zero compiles
            after warm-up, graceful shutdown
  kernels   every Pallas kernel compiled by Mosaic (`interpret=False`) at a
            BERT-base-sized shape its own `profitable` predicate accepts,
            and compared with its jnp reference

It exits non-zero, naming what it found, when the platform is not `tpu`, and
non-zero, naming the phase, on the first phase that fails.  When every phase
passed, the last line of stdout is one JSON object:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.

    python chip_smoke.py            # every phase
    python chip_smoke.py kernels    # only the named phases; prints no result

Wall times printed per phase include compilation: they are set-up times, not
metrics.  The jax compilation cache (hits and writes are printed per phase)
lives where `compile.place_compilation_cache` puts it.
"""
import io
import json
import sys
import threading
import time

import numpy as np

SEED = 0


def _finite(name, a):
    a = np.asarray(a, np.float32)
    if not np.isfinite(a).all():
        raise AssertionError(f"{name}: non-finite values")
    return a


def _check_falling(name, losses):
    losses = _finite(f"{name} loss", losses)
    print(f"[smoke] {name} loss per step: "
          + " ".join(f"{v:.4f}" for v in losses), flush=True)
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{name}: loss did not fall ({losses[0]:.4f} -> {losses[-1]:.4f})")


def _saved(model):
    """`model.save` into memory, rewound for `load`.  BERT-base with its Adam
    state is a 1.3 GB zip, and the machine that runs this may cap the size
    of a file (the driver's did: EFBIG), so the round trip touches no disk;
    the save-to-a-path spelling is what the CPU tests cover."""
    buf = io.BytesIO()
    model.save(buf)
    print(f"[smoke] saved {type(model).__name__}: "
          f"{buf.tell() / 2**20:.0f} MiB zip, in memory", flush=True)
    buf.seek(0)
    return buf


def _same_leaves(name, a, b):
    import jax
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb) or not all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(la, lb)):
        raise AssertionError(f"{name}: parameters differ after save -> load")


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / (np.max(np.abs(want)) + 1e-12))


# ---------------------------------------------------------------------------
# resnet50: train, then use the trained net
# ---------------------------------------------------------------------------

def phase_resnet50(batch=64, image=224, classes=1000, n_fit=8, k=2):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.train.updaters import Nesterovs
    from deeplearning4j_tpu.zoo import ResNet50

    rng = np.random.RandomState(SEED)
    x = jnp.asarray(rng.rand(batch, image, image, 3).astype(np.float32))
    y = jnp.asarray(
        np.eye(classes, dtype=np.float32)[rng.randint(0, classes, batch)])
    net = ResNet50(n_classes=classes, input_shape=(image, image, 3),
                   updater=Nesterovs(0.01, 0.9),
                   compute_dtype="bfloat16").init_model()

    losses = []
    for _ in range(n_fit):
        net.fit(x, y)
        losses.append(net.score_array())
    block = net.fit_steps(jnp.broadcast_to(x, (k,) + x.shape),
                          jnp.broadcast_to(y, (k,) + y.shape))
    jax.block_until_ready(net.params_)
    _check_falling("resnet50", [*losses, *np.asarray(block)])

    # the trained net after its buffers went through donated steps
    (out,) = net.output(x)
    out = _finite("resnet50 output", out)
    if out.shape != (batch, classes):
        raise AssertionError(f"resnet50 output shape {out.shape}")
    if not np.allclose(out.sum(-1), 1.0, atol=1e-2):
        raise AssertionError("resnet50 output rows are not distributions")
    _finite("resnet50 score", net.score())

    loaded = ComputationGraph.load(_saved(net))
    _same_leaves("resnet50", net.params_, loaded.params_)
    (out2,) = loaded.output(x)
    if _rel_err(out2, out) > 1e-5:
        raise AssertionError("resnet50: loaded net answers differently")
    loaded.fit(x, y)                    # the loaded updater state trains on
    _finite("resnet50 loaded-net loss", loaded.score())


# ---------------------------------------------------------------------------
# bert: train, then use the trained model
# ---------------------------------------------------------------------------

def phase_bert(config=None, batch=64, n_fit=4, k=2):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.ops import norm_kernels
    from deeplearning4j_tpu.ops import pallas as tier
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo import BertConfig, BertModel

    cfg = config or BertConfig.base(max_len=128, compute_dtype="bfloat16")
    t = cfg.max_len
    model = BertModel(cfg, updater=Adam(1e-4))
    rng = np.random.RandomState(SEED)
    ids = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (batch, t)).astype(np.int32))
    mask = jnp.ones((batch, t), jnp.float32)
    lmask = jnp.asarray((rng.rand(batch, t) < 0.15).astype(np.float32))
    mds = MultiDataSet(features=[ids, mask], labels=[ids],
                       labels_masks=[lmask])

    # where dispatch says `pallas`, the lowered step must hold Mosaic calls
    hidden = jax.ShapeDtypeStruct((batch, t, cfg.hidden), jnp.bfloat16)
    heads = jax.ShapeDtypeStruct(
        (batch, cfg.n_heads, t, cfg.hidden // cfg.n_heads), jnp.bfloat16)
    want_ln = (jax.default_backend() == "tpu"
               and norm_kernels._can_tile(hidden)
               and norm_kernels._worth_it(hidden))
    want_flash = tier.dispatch.resolve(
        "attention", heads, heads, heads,
        mask=jax.ShapeDtypeStruct((batch, t), jnp.bfloat16)) == "pallas"
    text = model._step("mlm").lower(
        model.params_, model.opt_state_, jnp.int32(0), jnp.int32(0),
        ids, mask, ids, lmask).as_text()
    n_mosaic = text.count("tpu_custom_call")
    print(f"[smoke] bert step: dispatch says fused LN "
          f"{'pallas' if want_ln else 'reference'}, attention "
          f"{'pallas' if want_flash else 'reference'}; lowered text holds "
          f"{n_mosaic} Mosaic calls", flush=True)
    if (n_mosaic > 0) != (want_ln or want_flash):
        raise AssertionError(
            "bert: Mosaic calls in the lowered step disagree with dispatch")

    losses = [model.fit_batch(mds) for _ in range(n_fit)]
    stacked = MultiDataSet(
        features=[jnp.broadcast_to(f, (k,) + f.shape) for f in mds.features],
        labels=[jnp.broadcast_to(l, (k,) + l.shape) for l in mds.labels],
        labels_masks=[jnp.broadcast_to(lmask, (k,) + lmask.shape)])
    block = model.fit_steps(stacked)
    jax.block_until_ready(model.params_)
    _check_falling("bert", [*losses, *np.asarray(block)])
    head = model.mlm_head_stats()
    print(f"[smoke] bert MLM head: {head['gathered_steps']} of "
          f"{head['steps']} steps in one pass of {head['capacity']} rows, "
          f"most labelled positions {head['max_labelled']} of {batch * t}",
          flush=True)
    if head["steps"] != n_fit + k or head["fallback_steps"]:
        raise AssertionError(f"bert: MLM head counters {head}")

    logits = _finite("bert output_mlm", model.output_mlm(ids[:4], mask[:4]))
    if logits.shape != (4, t, cfg.vocab_size):
        raise AssertionError(f"bert output_mlm shape {logits.shape}")
    _finite("bert score", model.score())

    loaded = BertModel.load(_saved(model))
    _same_leaves("bert", model.params_, loaded.params_)
    _same_leaves("bert updater state", model.opt_state_, loaded.opt_state_)
    _finite("bert loaded-model loss", loaded.fit_batch(mds))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(image=224, classes=1000, max_batch=4, n_clients=6):
    from deeplearning4j_tpu.serving import ModelServer

    srv = ModelServer(max_batch=max_batch, batch_timeout_ms=2.0)
    try:
        entry = srv.deploy("resnet50", zoo="ResNet50", warmup=True,
                           n_classes=classes, input_shape=(image, image, 3))
        warm = dict(srv.stats()["compile_cache"])
        print(f"[smoke] serve: buckets {srv.cache.buckets} warmed, "
              f"compile cache {warm}", flush=True)

        rng = np.random.RandomState(SEED)
        reqs = [rng.rand(1 + i % max_batch, image, image, 3)
                .astype(np.float32) for i in range(n_clients)]
        replies = [None] * n_clients

        def client(i):
            replies[i] = srv.output("resnet50", reqs[i], deadline_ms=60000.0,
                                    timeout=120.0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180.0)
        if any(th.is_alive() for th in threads):
            raise AssertionError("serve: a client thread did not finish")
        for i, (x, r) in enumerate(zip(reqs, replies)):
            if r is None:
                raise AssertionError(f"serve: request {i} got no reply")
            r = _finite(f"serve reply {i}", r)
            if r.shape != (x.shape[0], classes):
                raise AssertionError(f"serve: reply {i} shape {r.shape}")
        after = srv.stats()["compile_cache"]
        if after["misses"] != warm["misses"]:
            raise AssertionError(
                f"serve: compiled after warm-up ({warm} -> {after})")
        # the batched, padded, bucketed path against the direct forward
        (direct,) = entry.model.output(reqs[-1])
        err = _rel_err(replies[-1], direct)
        print(f"[smoke] serve: {n_clients} replies, compile cache {after}, "
              f"reply vs direct forward rel err {err:.2e}", flush=True)
        if err > 1e-3:
            raise AssertionError("serve: reply differs from direct forward")
    finally:
        srv.shutdown()
    if srv.readyz()["ready"]:
        raise AssertionError("serve: still ready after shutdown")


# ---------------------------------------------------------------------------
# kernels: Mosaic compiles each one; it matches its reference
# ---------------------------------------------------------------------------

def phase_kernels(rows=8192, hidden=768, inter=3072, seq=2048, n_heads=12,
                  d_head=64, pages_per_seq=64, interpret=False):
    """`interpret` is for running this phase's logic on a CPU while
    debugging; `main` never sets it."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import attention_kernels as ak
    from deeplearning4j_tpu.ops import norm_kernels as nk
    from deeplearning4j_tpu.ops import pallas as tier
    from deeplearning4j_tpu.ops.pallas import matmul as pm
    from deeplearning4j_tpu.ops.pallas import paged_attention as pp
    from deeplearning4j_tpu.ops.quant_kernels import quantize_tensor

    rng = np.random.RandomState(SEED)
    on_chip = tier.dispatch.on_accelerator()
    failures = []

    def check(name, got, want, tol, picked=True):
        errs = [_rel_err(_finite(name, g), w) for g, w in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))]
        ok = max(errs) <= tol and (picked or not on_chip)
        print(f"[smoke] kernel {name}: max rel err {max(errs):.2e} "
              f"(tol {tol:.0e}), auto dispatch "
              f"{'pallas' if picked else 'reference'}"
              f"{'' if ok else '  <-- FAIL'}", flush=True)
        if not ok:
            failures.append(name)

    def randn(shape, dtype, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    # -- fused LayerNorm, forward and backward ------------------------------
    for dt, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 2e-2)):
        x = randn((rows, hidden), dt)
        g = randn((hidden,), jnp.float32, 0.1) + 1.0
        b = randn((hidden,), jnp.float32, 0.1)
        dy = randn((rows, hidden), dt)
        picked = nk._can_tile(x) and nk._worth_it(x)

        def ln_k(x, g, b):
            return nk._fused_ln(x, g, b, 1e-12, interpret)

        def ln_r(x, g, b):
            return nk.layer_norm_reference(
                x.astype(jnp.float32), g, b, 1e-12).astype(x.dtype)

        name = f"fused_ln[{jnp.dtype(dt).name}]"
        check(name + " fwd", jax.jit(ln_k)(x, g, b), jax.jit(ln_r)(x, g, b),
              tol, picked)

        def grads(f):
            return jax.jit(lambda x, g, b: jax.vjp(f, x, g, b)[1](dy))

        check(name + " bwd", grads(ln_k)(x, g, b), grads(ln_r)(x, g, b),
              tol, picked)

    # -- flash attention, forward and backward, masked and causal -----------
    q, k, v, do = (randn((1, n_heads, seq, d_head), jnp.bfloat16, 0.5)
                   for _ in range(4))
    keep = jnp.asarray(rng.rand(1, seq) < 0.9, jnp.bfloat16)
    for causal in (False, True):
        picked = tier.dispatch.resolve("attention", q, k, v, mask=keep,
                                       causal=causal) == "pallas"

        def fa_k(q, k, v):
            return tier.attention.flash_attention(
                q, k, v, mask=keep, causal=causal,
                tile=tier.dispatch.get_tile("attention"),
                interpret=interpret)

        def fa_r(q, k, v):
            with jax.default_matmul_precision("highest"):
                return ak.mha_reference(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), mask=keep, causal=causal)

        name = f"flash_attention[bf16 masked{' causal' if causal else ''}]"
        check(name + " fwd", jax.jit(fa_k)(q, k, v), jax.jit(fa_r)(q, k, v),
              2e-2, picked)
        check(name + " bwd",
              jax.jit(lambda q, k, v: jax.vjp(fa_k, q, k, v)[1](do))(q, k, v),
              jax.jit(lambda q, k, v: jax.vjp(fa_r, q, k, v)[1](
                  do.astype(jnp.float32)))(q, k, v),
              3e-2, picked)

    # -- matmul family at the BERT-base FFN shape ---------------------------
    x = randn((rows, hidden), jnp.bfloat16)
    w = randn((hidden, inter), jnp.bfloat16, 0.05)
    bias = randn((inter,), jnp.float32, 0.1)

    def highest(f):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(run)

    for act in ("gelu", "tanh", "sigmoid", "relu"):
        check(f"fused_dense[bf16 {act}]",
              jax.jit(lambda x, w, b: pm.fused_dense(
                  x, w, b, act, tile=tier.dispatch.get_tile("fused_dense"),
                  interpret=interpret))(x, w, bias),
              highest(lambda x, w, b: pm.fused_dense_reference(
                  x.astype(jnp.float32), w.astype(jnp.float32), b, act))(
                      x, w, bias),
              2e-2,
              tier.dispatch.resolve("fused_dense", x, w, bias=bias,
                                    activation=act) == "pallas")

    qt = quantize_tensor(np.asarray(w, np.float32))
    check("q_matmul[bf16 x int8]",
          jax.jit(lambda x, wq, s, b: pm.q_matmul(
              x, wq, s, b, tile=tier.dispatch.get_tile("q_matmul"),
              interpret=interpret))(x, qt.q, qt.scale, bias),
          highest(lambda x, wq, s, b: pm.q_matmul_reference(
              x.astype(jnp.float32), wq, s, b))(x, qt.q, qt.scale, bias),
          2e-2,
          tier.dispatch.resolve("q_matmul", x, qt.q, qt.scale,
                                bias=bias) == "pallas")

    xq = jnp.asarray(rng.randint(-127, 128, (rows, hidden)), jnp.int8)
    check("int8_matmul[int8 x int8]",
          jax.jit(lambda xq, wq, s, b: pm.int8_matmul(
              xq, wq, s, 0.02, b, tile=tier.dispatch.get_tile("int8_matmul"),
              interpret=interpret))(xq, qt.q, qt.scale, bias),
          jax.jit(lambda xq, wq, s, b: pm.int8_matmul_reference(
              xq, wq, s, 0.02, b))(xq, qt.q, qt.scale, bias),
          1e-5,
          tier.dispatch.resolve("int8_matmul", xq, qt.q, qt.scale,
                                jnp.float32(0.02), bias=bias) == "pallas")

    # -- paged decode attention, f32 and int8 pages -------------------------
    page = tier.DEFAULT_TILES["paged_attention"].block_kv
    n_seqs = 8
    n_pages = n_seqs * pages_per_seq
    qd = randn((n_seqs, n_heads, d_head), jnp.float32, 0.5)
    kp = rng.randn(n_pages, page, n_heads, d_head).astype(np.float32) * 0.5
    vp = rng.randn(n_pages, page, n_heads, d_head).astype(np.float32) * 0.5
    tables = jnp.asarray(rng.permutation(n_pages).reshape(
        n_seqs, pages_per_seq), jnp.int32)
    lens = jnp.asarray(rng.randint(1, pages_per_seq * page + 1, n_seqs),
                       jnp.int32)

    def paged_pair(name, kq, vq, ks=None, vs=None, tol=2e-2):
        picked = tier.dispatch.resolve(
            "paged_attention", qd, kq, vq, tables, lens,
            k_scales=ks, v_scales=vs) == "pallas"
        got = jax.jit(lambda: pp.paged_attention(
            qd, kq, vq, tables, lens, k_scales=ks, v_scales=vs,
            interpret=interpret))()
        want = highest(lambda: pp.paged_attention_reference(
            qd, kq, vq, tables, lens, k_scales=ks, v_scales=vs))()
        check(name, got, want, tol, picked)

    paged_pair("paged_attention[f32 pages]", jnp.asarray(kp), jnp.asarray(vp))
    kq = quantize_tensor(kp.reshape(-1, d_head), axis=0)
    vq = quantize_tensor(vp.reshape(-1, d_head), axis=0)
    shp = (n_pages, page, n_heads)
    paged_pair("paged_attention[int8 pages]",
               kq.q.reshape(shp + (d_head,)), vq.q.reshape(shp + (d_head,)),
               kq.scale.reshape(shp), vq.scale.reshape(shp))

    if failures:
        raise AssertionError(f"kernels failed: {failures}")


PHASES = {"resnet50": phase_resnet50, "bert": phase_bert,
          "serve": phase_serve, "kernels": phase_kernels}


def main(argv):
    import importlib.metadata as md

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"[smoke] jax {md.version('jax')} jaxlib {md.version('jaxlib')} "
          f"libtpu {md.version('libtpu')} platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: found platform={device['platform']!r} "
              f"({device['kind']} x{device['count']}), need 'tpu'",
              file=sys.stderr)
        return 1

    from deeplearning4j_tpu.compile import place_compilation_cache
    print(f"[smoke] compilation cache at {place_compilation_cache()} "
          f"(jax_compilation_cache_dir="
          f"{jax.config.jax_compilation_cache_dir})", flush=True)
    cache = {"requests": 0, "hits": 0, "writes": 0}
    events = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "writes"}

    def count(event, **kw):
        if event in events:
            cache[events[event]] += 1

    jax.monitoring.register_event_listener(count)

    names = argv or list(PHASES)
    unknown = [n for n in names if n not in PHASES]
    if unknown:
        print(f"chip_smoke: unknown phase(s) {unknown}; have {list(PHASES)}",
              file=sys.stderr)
        return 2
    t_all = time.perf_counter()
    for name in names:
        before, t0 = dict(cache), time.perf_counter()
        print(f"[smoke] phase {name}: start", flush=True)
        try:
            PHASES[name]()
        except BaseException:
            print(f"chip_smoke: FAILED in phase {name}", file=sys.stderr,
                  flush=True)
            raise
        print(f"[smoke] phase {name}: ok in "
              f"{time.perf_counter() - t0:.1f} s (set-up time, compilation "
              f"included); compilation cache "
              + ", ".join(f"{k} {cache[k] - before[k]}" for k in cache),
              flush=True)
    print(f"[smoke] all of {names} ok in "
          f"{time.perf_counter() - t_all:.1f} s; at exit "
          f"jax_compilation_cache_dir="
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    if names != list(PHASES):
        return 0                        # a partial run proves nothing whole
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
