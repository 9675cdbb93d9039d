"""ModelSerializer — checkpoint read/write.

Reference: `deeplearning4j-nn/.../util/ModelSerializer.java` — a zip holding
`configuration.json` + `coefficients.bin` (flat param buffer) + updater
state (+ optional normalizer).  The format here keeps those exact semantics
(exact-resume: updater state incl. iteration/epoch counters round-trips) with
the same member names, so tooling expectations carry over; tensor payloads
are raw little-endian buffers with a JSON manifest of shapes/dtypes.

For sharded multi-host checkpoints see parallel/ (orbax-backed); this module
is the single-process contract used by CheckpointListener and save/load.
"""
from __future__ import annotations

import io
import json
import zipfile
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

CONFIG_JSON = "configuration.json"
COEFFICIENTS_BIN = "coefficients.bin"
UPDATER_BIN = "updaterState.bin"
STATE_BIN = "layerState.bin"
MANIFEST_JSON = "manifest.json"
NORMALIZER_BIN = "normalizer.bin"


def _to_host(leaf) -> np.ndarray:
    """Device array -> host numpy, including multi-process global arrays:
    a replicated array spans non-addressable (remote) devices, but every
    process holds a complete local copy — read that shard.  Partition-
    sharded leaves must be all-gathered first (parallel.multihost
    .allgather_params), same contract as the reference's Spark
    driver-side param sync before ModelSerializer."""
    if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
        shard = leaf.addressable_data(0)
        if shard.shape != leaf.shape:
            raise ValueError(
                "Cannot checkpoint a partition-sharded array from one "
                "process — gather it first (multihost.allgather_params)")
        return np.asarray(shard)
    return np.asarray(leaf)


def _tree_to_flat(tree: Any):
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return b"", []
    host = [_to_host(l) for l in leaves]
    manifest = [{"shape": list(l.shape), "dtype": str(l.dtype)}
                for l in host]
    buf = b"".join(np.ascontiguousarray(l).tobytes() for l in host)
    return buf, manifest


def _flat_to_tree(template: Any, buf: bytes, manifest):
    leaves, treedef = jax.tree_util.tree_flatten(template)
    out, off = [], 0
    for tmpl, m in zip(leaves, manifest):
        dt = np.dtype(m["dtype"])
        n = int(np.prod(m["shape"])) if m["shape"] else 1
        arr = np.frombuffer(buf, dt, count=n, offset=off).reshape(m["shape"])
        off += n * dt.itemsize
        out.append(jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


def write_model(net, path, save_updater: bool = True,
                normalizer=None) -> None:
    """`path` is a file name or a seekable binary file object (the
    reference's `writeModel` likewise takes a File or an OutputStream)."""
    params_buf, params_manifest = _tree_to_flat(net.params_)
    state_buf, state_manifest = _tree_to_flat(net.state_)
    manifest = {
        "format": "deeplearning4j_tpu.model.v1",
        "iteration": net.iteration,
        "epoch": net.epoch,
        "params": params_manifest,
        "state": state_manifest,
    }
    upd_buf = b""
    if save_updater and net.opt_state_ is not None:
        upd_buf, upd_manifest = _tree_to_flat(net.opt_state_)
        manifest["updater"] = upd_manifest
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr(CONFIG_JSON, net.conf.to_json())
        z.writestr(MANIFEST_JSON, json.dumps(manifest))
        z.writestr(COEFFICIENTS_BIN, params_buf)
        z.writestr(STATE_BIN, state_buf)
        if upd_buf:
            z.writestr(UPDATER_BIN, upd_buf)
        if normalizer is not None:
            z.writestr(NORMALIZER_BIN, normalizer.to_bytes())


def read_model(path, load_updater: bool = True):
    """Restore either model class; dispatch on the config `format` tag (the
    reference's ModelSerializer likewise restores MultiLayerNetwork or
    ComputationGraph from one zip format).  `path` is a file name or a
    seekable binary file object."""
    from deeplearning4j_tpu.nn.multilayer import (
        MultiLayerConfiguration, MultiLayerNetwork)
    from deeplearning4j_tpu.nn.graph import (
        ComputationGraph, ComputationGraphConfiguration)
    with zipfile.ZipFile(path, "r") as z:
        conf_json = z.read(CONFIG_JSON).decode()
        manifest = json.loads(z.read(MANIFEST_JSON).decode())
        if "ComputationGraphConfiguration" in json.loads(conf_json).get("format", ""):
            net = ComputationGraph(
                ComputationGraphConfiguration.from_json(conf_json)).init()
        else:
            net = MultiLayerNetwork(
                MultiLayerConfiguration.from_json(conf_json)).init()
        net.params_ = _flat_to_tree(net.params_, z.read(COEFFICIENTS_BIN),
                                    manifest["params"])
        net.state_ = _flat_to_tree(net.state_, z.read(STATE_BIN),
                                   manifest["state"])
        net.iteration = manifest["iteration"]
        net.epoch = manifest["epoch"]
        if load_updater and UPDATER_BIN in z.namelist() and "updater" in manifest:
            net.opt_state_ = _flat_to_tree(net.opt_state_, z.read(UPDATER_BIN),
                                           manifest["updater"])
    return net


def read_normalizer(path: str, cls) -> Optional[Any]:
    with zipfile.ZipFile(path, "r") as z:
        if NORMALIZER_BIN not in z.namelist():
            return None
        return cls.from_bytes(z.read(NORMALIZER_BIN))
