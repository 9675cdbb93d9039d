"""Per-op validation harness.

Reference: `nd4j-api/src/main/java/org/nd4j/autodiff/validation/
OpValidation.java` + `TestCase.java` — the framework that checks, for every
registered op: forward value against a golden, the op's shape function
against the executed output, and the analytic gradient against a central
finite difference, while tracking coverage of the whole registry so
never-tested ops fail the build.

TPU-native mapping of those semantics:

- *forward value*: run the `OP_TABLE` entry eagerly on numpy inputs and
  compare against an independent golden (numpy/scipy/torch closed form) or
  a property validator.
- *shape function*: in jax the "shape function" is abstract evaluation —
  `jax.eval_shape` traces the op without running it.  The harness checks
  that the abstract output (shape AND dtype) of every traced op matches
  the concrete result, and that the op compiles and agrees under
  `jax.jit` (a stronger contract than the reference's: declarable ops
  here must be trace-compatible to be usable in SameDiff graphs at all).
- *gradient*: analytic `jax.grad` of a fixed random scalar projection of
  the outputs vs a float64 central finite difference, per differentiable
  tensor argument.
- *coverage*: `coverage_report` diffs the case list against the live
  registry; the test suite fails on any op with neither a case nor an
  allowlist entry (and on stale allowlist entries), exactly like the
  reference's `OpValidation.logCoverageInformation` gate.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

__all__ = ["OpTestCase", "validate_case", "coverage_report"]


@dataclasses.dataclass
class OpTestCase:
    """One validation case for a registry op (reference `TestCase`)."""

    op: str
    args: Tuple[Any, ...] = ()
    kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: numpy value(s) or callable(*np_args, **kwargs) -> value(s)
    golden: Any = None
    #: alternative validator: callable(out_leaves: list[np.ndarray]) that
    #: raises on failure — for ops whose value is checked by property
    #: (random sampling moments, round-trips, decode-of-encode, ...)
    check: Optional[Callable] = None
    #: tensor-arg indices to finite-difference gradient-check
    grad: Tuple[int, ...] = ()
    #: if > 0, FD-check only this many seeded random coordinates per arg
    #: (the reference's `TestCase.gradCheckMaxPerParam` — keeps big-input
    #: ops affordable).  `OPVAL_FULL=1` in the env removes the cap.
    grad_sample: int = 0
    tol: float = 1e-5
    gtol: float = 5e-3
    #: also compile under jit + check eval_shape agreement (off for
    #: host-side/ragged ops, which the reference likewise executes eagerly)
    jit: bool = True
    #: fully custom validation — callable(fn) run instead of the pipeline
    #: (TensorList stateful ops, tuple-input ops)
    custom: Optional[Callable] = None
    #: distinguishes multiple cases for one op in test ids
    tag: str = ""

    @property
    def id(self) -> str:
        return f"{self.op}{'-' + self.tag if self.tag else ''}"


def _leaves(out):
    """Flatten an op result (array / tuple / nested) to array leaves."""
    if isinstance(out, (tuple, list)):
        acc = []
        for o in out:
            acc.extend(_leaves(o))
        return acc
    return [out]


def _to_np(leaf):
    return np.asarray(leaf)


def _is_tensor_arg(a) -> bool:
    return isinstance(a, np.ndarray)


def _compare(got, want, tol, what):
    got_l = [_to_np(g) for g in _leaves(got)]
    want_l = [_to_np(w) for w in _leaves(want)]
    assert len(got_l) == len(want_l), (
        f"{what}: output arity {len(got_l)} != golden arity {len(want_l)}")
    for i, (g, w) in enumerate(zip(got_l, want_l)):
        assert tuple(g.shape) == tuple(w.shape), (
            f"{what} leaf {i}: shape {g.shape} != golden {w.shape}")
        if g.dtype == bool or np.issubdtype(g.dtype, np.integer):
            np.testing.assert_array_equal(
                g, w.astype(g.dtype), err_msg=f"{what} leaf {i}")
        elif np.issubdtype(g.dtype, np.complexfloating):
            # compare as complex — a float64 cast would silently drop
            # the imaginary half of every FFT-family check
            np.testing.assert_allclose(
                g.astype(np.complex128), w.astype(np.complex128),
                rtol=tol, atol=tol, err_msg=f"{what} leaf {i}")
        else:
            np.testing.assert_allclose(
                g.astype(np.float64), w.astype(np.float64),
                rtol=tol, atol=tol, err_msg=f"{what} leaf {i}")


def validate_case(case: OpTestCase) -> None:
    """Run the full forward/shape/jit/grad pipeline for one case."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.autodiff.ops import OP_TABLE

    fn = OP_TABLE[case.op]
    if case.custom is not None:
        case.custom(fn)
        return

    tensor_idx = [i for i, a in enumerate(case.args) if _is_tensor_arg(a)]
    jargs = [jnp.asarray(a) if _is_tensor_arg(a) else a for a in case.args]

    # 1. forward (eager)
    out = fn(*jargs, **case.kwargs)

    # 2. value vs golden / property check
    if case.golden is not None:
        want = (case.golden(*[np.asarray(a) if _is_tensor_arg(a) else a
                              for a in case.args], **case.kwargs)
                if callable(case.golden) else case.golden)
        _compare(out, want, case.tol, f"{case.id} forward")
    if case.check is not None:
        case.check([_to_np(o) for o in _leaves(out)])

    # 3. shape function (eval_shape) + jit agreement
    if case.jit and tensor_idx:
        def closure(*tensors):
            full = list(jargs)
            for i, t in zip(tensor_idx, tensors):
                full[i] = t
            return fn(*full, **case.kwargs)

        tensors = [jargs[i] for i in tensor_idx]
        abstract = jax.eval_shape(closure, *tensors)
        a_l = _leaves(abstract)
        o_l = _leaves(out)
        assert len(a_l) == len(o_l), (
            f"{case.id}: eval_shape arity {len(a_l)} != executed "
            f"{len(o_l)}")
        for i, (a, o) in enumerate(zip(a_l, o_l)):
            o = jnp.asarray(o)
            assert tuple(a.shape) == tuple(o.shape), (
                f"{case.id} leaf {i}: abstract shape {a.shape} != "
                f"executed {o.shape}")
            assert a.dtype == o.dtype, (
                f"{case.id} leaf {i}: abstract dtype {a.dtype} != "
                f"executed {o.dtype}")
        out_j = jax.jit(closure)(*tensors)
        _compare(out_j, [_to_np(o) for o in _leaves(out)],
                 max(case.tol, 1e-6), f"{case.id} jit-vs-eager")

    # 4. gradient: analytic vs central finite difference (float64)
    if case.grad:
        _check_grad(fn, case, tensor_idx)


def _compiled_first(f, *args):
    """`f(*args)` as one compiled program, or eagerly where an op's
    Python needs concrete values: one compile, not one a primitive."""
    import jax

    try:
        return jax.jit(f)(*args)
    except Exception:                   # not traceable -> run it eagerly
        return f(*args)


def _check_grad(fn, case: OpTestCase, tensor_idx) -> None:
    import jax

    # The central difference with eps=1e-5 is below float32 noise:
    # without x64 enabled jnp.asarray silently downcasts the f64 inputs
    # and the check produces spurious results.  Enable x64 locally so
    # validate_case is correct even outside the test suite's conftest.
    with jax.enable_x64(True):
        _check_grad_x64(fn, case, tensor_idx)


def _check_grad_x64(fn, case: OpTestCase, tensor_idx) -> None:
    import jax
    import jax.numpy as jnp

    f64_args = [
        a.astype(np.float64) if (_is_tensor_arg(a)
                                 and np.issubdtype(a.dtype, np.floating))
        else a for a in case.args]
    rs = np.random.RandomState(7)

    # fixed random projection -> scalar loss over all float output leaves.
    # Only the output SHAPES/dtypes are needed to draw the weights, so
    # trace with eval_shape instead of paying a full eager x64 execution;
    # ops that resist abstract evaluation fall back to running eagerly.
    try:
        probe = jax.eval_shape(
            lambda: fn(*[jnp.asarray(a) if _is_tensor_arg(a) else a
                         for a in f64_args], **case.kwargs))
    except Exception:
        probe = fn(*[jnp.asarray(a) if _is_tensor_arg(a) else a
                     for a in f64_args], **case.kwargs)

    def _pdtype(p):
        d = getattr(p, "dtype", None)
        return d if d is not None else np.asarray(p).dtype

    weights = [rs.uniform(0.5, 1.5, np.shape(p)).astype(np.float64)
               if np.issubdtype(_pdtype(p), np.floating) else None
               for p in _leaves(probe)]

    def loss_at(vals):
        full = list(vals)
        out = fn(*[jnp.asarray(a) if _is_tensor_arg(a) else a
                   for a in full], **case.kwargs)
        total = 0.0
        for p, w in zip(_leaves(out), weights):
            if w is not None:
                total = total + jnp.sum(jnp.asarray(p) * w)
        return total

    import os

    for gi in case.grad:
        assert gi in tensor_idx, (
            f"{case.id}: grad index {gi} is not a tensor arg")
        assert np.issubdtype(f64_args[gi].dtype, np.floating), (
            f"{case.id}: grad arg {gi} is not float")

    # one trace for all checked args (argnums), then per-arg FD
    def loss_args(*xs):
        vals = list(f64_args)
        for i, x in zip(case.grad, xs):
            vals[i] = x
        return loss_at(vals)

    analytic_all = _compiled_first(
        jax.grad(loss_args, argnums=tuple(range(len(case.grad)))),
        *[jnp.asarray(f64_args[i]) for i in case.grad])

    sample = 0 if os.environ.get("OPVAL_FULL") else case.grad_sample
    eps = 1e-5
    for pos, gi in enumerate(case.grad):
        x0 = f64_args[gi]
        analytic = np.asarray(analytic_all[pos])
        flat = x0.reshape(-1)
        if sample and flat.size > sample:
            coords = np.random.RandomState(0xC0FFEE + gi).choice(
                flat.size, sample, replace=False)
        else:
            coords = np.arange(flat.size)

        def loss_wrt(x):
            vals = list(f64_args)
            vals[gi] = x
            return loss_at(vals)

        # Batched central difference: evaluate every +eps/-eps perturbation
        # in ONE vmapped call instead of 2*len(coords) eager dispatches —
        # same coordinates, same eps, same tolerance, ~n× less per-op
        # dispatch overhead.  Ops without batching rules (or whose python
        # shape logic rejects the traced call) fall back to the scalar
        # loop below, so vectorization never changes which cases pass.
        try:
            n = len(coords)
            xs = np.tile(flat, (2 * n, 1))
            xs[np.arange(n), coords] += eps
            xs[np.arange(n, 2 * n), coords] -= eps
            vals = np.asarray(_compiled_first(
                jax.vmap(loss_wrt),
                jnp.asarray(xs.reshape((2 * n,) + x0.shape))))
        except Exception:
            vals = None                 # not vmappable -> scalar fallback
        if vals is not None:
            fd = (vals[:n] - vals[n:]) / (2 * eps)
            np.testing.assert_allclose(
                analytic.reshape(-1)[coords], fd, rtol=case.gtol,
                atol=case.gtol,
                err_msg=f"{case.id} grad wrt arg {gi} (batched FD)")
            continue

        for k in coords:
            xp = flat.copy()
            xm = flat.copy()
            xp[k] += eps
            xm[k] -= eps
            lp = float(loss_wrt(jnp.asarray(xp.reshape(x0.shape))))
            lm = float(loss_wrt(jnp.asarray(xm.reshape(x0.shape))))
            fd = (lp - lm) / (2 * eps)
            np.testing.assert_allclose(
                analytic.reshape(-1)[k], fd, rtol=case.gtol,
                atol=case.gtol,
                err_msg=f"{case.id} grad wrt arg {gi} coord {k}")


def coverage_report(cases: Sequence[OpTestCase],
                    allowlist: Dict[str, str]):
    """Diff the case list against the live registry.

    Returns (missing, stale_allowlist, unknown_ops, value_checked_pct):
    - missing: registered ops with neither a case nor an allowlist entry
    - stale: allowlist entries that DO have a case (keep the list honest)
    - unknown: cases/allowlist naming ops not in the registry
    - value_checked_pct: fraction of registered ops with at least one
      case carrying a golden or a property check
    """
    from deeplearning4j_tpu.autodiff.ops import OP_TABLE

    registered = set(OP_TABLE)
    tested = {c.op for c in cases}
    value_checked = {c.op for c in cases
                     if c.golden is not None or c.check is not None
                     or c.custom is not None}
    missing = sorted(registered - tested - set(allowlist))
    stale = sorted(set(allowlist) & tested)
    unknown = sorted((tested | set(allowlist)) - registered)
    pct = len(value_checked & registered) / max(len(registered), 1)
    return missing, stale, unknown, pct
