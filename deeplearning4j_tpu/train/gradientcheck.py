"""Gradient checking — central finite differences vs analytic gradients.

Reference: `GradientCheckUtil`
(`deeplearning4j-nn/.../gradientcheck/GradientCheckUtil.java`), used by the
`GradientCheckTests` family: perturb each parameter by ±eps in float64,
compare (f(p+e)-f(p-e))/2e against backprop, fail on max relative error.

Here the analytic side is `jax.grad` of the same scored function; the check
runs with `jax.enable_x64` semantics by casting params/data to float64 on
CPU (matching the reference's double-precision requirement for checks).
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def check_gradients(score_fn: Callable[[Any], jnp.ndarray], params: Any,
                    epsilon: float = 1e-6, max_rel_error: float = 1e-3,
                    min_abs_error: float = 1e-8,
                    max_params_per_leaf: Optional[int] = 64,
                    seed: int = 12345, verbose: bool = False) -> bool:
    """Returns True if all checked parameters pass.

    score_fn must be a pure scalar function of the params pytree.  For leaves
    larger than `max_params_per_leaf`, a random subset of coordinates is
    checked (the reference checks all; subsetting keeps CI time sane —
    pass None to check every coordinate).
    """
    if jnp.array(np.float64(0.0)).dtype != jnp.float64:
        raise RuntimeError(
            "Gradient checks need float64: enable x64 first "
            "(jax.config.update('jax_enable_x64', True)) and run on CPU "
            "(JAX_PLATFORMS=cpu) — TPUs have no f64.")
    # NOTE: np.asarray of a device array can be non-C-contiguous, where
    # reshape(-1) silently copies and in-place perturbations are lost.
    # Flat contiguous 1-D copies are therefore the source of truth; leaves
    # are rebuilt from them at every evaluation.
    params64 = jax.tree_util.tree_map(
        lambda p: np.asarray(p, np.float64).copy(), params)
    analytic = jax.grad(lambda p: score_fn(p))(
        jax.tree_util.tree_map(jnp.array, params64))
    analytic = jax.tree_util.tree_map(np.asarray, analytic)

    rng = np.random.default_rng(seed)
    leaves_p, treedef = jax.tree_util.tree_flatten(params64)
    leaves_g = treedef.flatten_up_to(analytic)
    shapes = [l.shape for l in leaves_p]
    flats = [np.ascontiguousarray(l).ravel().copy() for l in leaves_p]

    def eval_score() -> float:
        # jnp.array (copy=True) — never hand jax a buffer we later mutate.
        tree = jax.tree_util.tree_unflatten(
            treedef, [jnp.array(f.reshape(s)) for f, s in zip(flats, shapes)])
        return float(score_fn(tree))

    ok = True
    for li, (flat_p, g) in enumerate(zip(flats, leaves_g)):
        flat_g = np.ascontiguousarray(np.asarray(g)).ravel()
        n = flat_p.size
        idxs = (np.arange(n) if max_params_per_leaf is None or n <= max_params_per_leaf
                else rng.choice(n, max_params_per_leaf, replace=False))
        for i in idxs:
            orig = flat_p[i]
            flat_p[i] = orig + epsilon
            plus = eval_score()
            flat_p[i] = orig - epsilon
            minus = eval_score()
            flat_p[i] = orig
            numeric = (plus - minus) / (2 * epsilon)
            a = flat_g[i]
            abs_err = abs(numeric - a)
            denom = max(abs(numeric), abs(a))
            rel = abs_err / denom if denom > 0 else 0.0
            if rel > max_rel_error and abs_err > min_abs_error:
                ok = False
                if verbose:
                    print(f"leaf {li} idx {i}: analytic={a:.8g} "
                          f"numeric={numeric:.8g} rel={rel:.3g}")
    return ok
