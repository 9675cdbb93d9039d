"""Autoregressive decode engine: prefill/decode split over a paged KV
cache with token-level continuous batching.

Every serving path before this one was one-shot forward; the NLP surface
(`nlp/`, `ops/attention_kernels.py`) is hit token-by-token.  This module
is the serving half of that gap — the kernel half is
``ops/pallas/paged_attention.py`` — built from three ideas the serving
stack already trusts:

**Prefill through the bucket ladder.**  A prompt of length T is padded to
the power-of-two bucket ``bucket_for(T)`` (the exact ladder
``serving/compile_cache.py`` applies to batch rows, applied here to the
time axis) and run through one jitted prefill per bucket, so a
sequence-length-skewed flood compiles ``log2(max_prompt)`` programs at
``warmup()`` and ZERO after — the BucketedCompileCache economics, where a
fresh XLA compile is the single worst tail-latency event.

**Token-level continuous batching.**  After prefill a sequence enters the
decode loop: every step advances ALL active sequences by one token in two
jitted calls (QKV projection, then paged attention + output head), and
between steps sequences are admitted from the waiting queue and retired
the moment they finish — mid-flight, releasing their queue slot and KV
pages immediately (the `ContinuousBatcher.cancel` semantics, which this
engine generalizes from one-dispatch requests to many-step sequences).
The decode batch is padded to a power-of-two row bucket, so admits and
retires never change the traced shape.

**Paged KV.**  KV lives in fixed-size pages shared by every sequence
(:class:`PagedKVCache`): a free-list allocator (:class:`KVBlockAllocator`)
hands out pages, each sequence owns only a block table, and exhaustion
sheds (``KVCacheExhausted`` is a ``RejectedError``) instead of crashing —
so concurrent sequences are bounded by tokens actually held, not by
``n_sequences * max_len`` reservations.  ``kv_dtype="int8"`` stores pages
through the PR-10 quantization seam (``quantize_tensor(axis=0)``: one f32
scale per (token, head) row) for ~3.8x more tokens per HBM byte at ≤1%
parity; the KV dtype is folded into ``kernel_tier_fingerprint`` so f32-KV
and int8-KV programs never share a persisted executable.

Fleet integration lives in ``serving/fleet.py`` (``deploy_decode`` /
``generate``): decode engines join ``ModelFleet`` as first-class members
whose SLO series is *inter-token* p99 (``decode_inter_token_ms``), and
failover restarts a failed sequence from token 0 on another replica,
explicitly and counted (``decode_sequence_restarts_total``) — a decode
sequence's KV dies with its replica, so silent resume is impossible and
pretending otherwise would hide the cost.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from deeplearning4j_tpu.monitor.instrument import (DecodeInstruments,
                                                   decode_instruments)
from deeplearning4j_tpu.monitor.registry import MetricsRegistry
from deeplearning4j_tpu.ops.pallas import dispatch as kd
from deeplearning4j_tpu.ops.pallas import paged_attention as pa
from deeplearning4j_tpu.ops.quant_kernels import quantize_tensor
from deeplearning4j_tpu.serving.batcher import (DeadlineExceededError,
                                                RejectedError)
from deeplearning4j_tpu.serving.compile_cache import bucket_for, bucket_sizes
from deeplearning4j_tpu.serving.metrics import ServingMetrics
from deeplearning4j_tpu.utils.counters import HitMissCounters


class KVCacheExhausted(RejectedError):
    """The paged KV pool has no free page.  A `RejectedError`: the caller
    sheds the sequence (admission refuses it / a growing sequence retires
    with this error) — never a crash, never a silent truncation."""


# ---------------------------------------------------------------------------
# Free-list page allocator
# ---------------------------------------------------------------------------


class KVBlockAllocator:
    """Fixed pool of KV pages handed out through a free list.

    O(1) alloc/free, no compaction: pages are position-independent
    (sequences address them through block tables), so fragmentation in
    the usual sense cannot happen — any free page serves any sequence.
    `alloc` is all-or-nothing: a request for n pages either gets all n or
    raises `KVCacheExhausted` leaving the pool untouched."""

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError("need at least one KV block")
        self.num_blocks = int(num_blocks)
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._allocated: Set[int] = set()
        self.high_water = 0
        self._lock = threading.Lock()

    def alloc(self, n: int = 1) -> List[int]:
        with self._lock:
            if n > len(self._free):
                raise KVCacheExhausted(
                    f"KV pool exhausted: need {n} pages, "
                    f"{len(self._free)}/{self.num_blocks} free — shed")
            blocks = [self._free.pop() for _ in range(n)]
            self._allocated.update(blocks)
            self.high_water = max(self.high_water, len(self._allocated))
            return blocks

    def free(self, blocks: Sequence[int]) -> None:
        with self._lock:
            for b in blocks:
                if b not in self._allocated:
                    raise ValueError(f"double free of KV block {b}")
                self._allocated.remove(b)
                self._free.append(b)

    @property
    def in_use(self) -> int:
        with self._lock:
            return len(self._allocated)

    @property
    def free_count(self) -> int:
        with self._lock:
            return len(self._free)


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _SeqPages:
    blocks: List[int]
    length: int = 0


class PagedKVCache:
    """KV storage as `[num_blocks, page_size, H, D]` page pools plus
    per-sequence block tables (the layout contract of
    ``ops/pallas/paged_attention.py``).

    `dtype="f32"` stores float32 pages; `dtype="int8"` stores int8 pages
    with per-(token, head) f32 scales produced by the PR-10 seam
    (`quant_kernels.quantize_tensor(rows, axis=0)` over rows of D), which
    both paged-attention implementations dequantize identically.  Pages
    live in host numpy (writes are in-place token appends) and are handed
    to the jitted decode step per call; block-table slots past a
    sequence's last page hold 0 so skipped kernel DMAs stay in bounds."""

    def __init__(self, num_blocks: int, page_size: int, n_heads: int,
                 head_dim: int, dtype: str = "f32"):
        if dtype not in ("f32", "int8"):
            raise ValueError(f"kv dtype {dtype!r}: want 'f32' or 'int8'")
        self.page_size = int(page_size)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        self.allocator = KVBlockAllocator(num_blocks)
        shape = (int(num_blocks), self.page_size, self.n_heads,
                 self.head_dim)
        store = np.int8 if dtype == "int8" else np.float32
        self.k_pages = np.zeros(shape, store)
        self.v_pages = np.zeros(shape, store)
        if dtype == "int8":
            self.k_scales = np.ones(shape[:3], np.float32)
            self.v_scales = np.ones(shape[:3], np.float32)
        else:
            self.k_scales = self.v_scales = None
        self._seqs: Dict[int, _SeqPages] = {}
        self._lock = threading.Lock()

    # ---- sequence lifecycle ----
    def allocate(self, seq_id: int) -> None:
        with self._lock:
            if seq_id in self._seqs:
                raise ValueError(f"sequence {seq_id} already allocated")
            self._seqs[seq_id] = _SeqPages(blocks=[])

    def write(self, seq_id: int, k: np.ndarray, v: np.ndarray) -> None:
        """Append T tokens of KV (`k`/`v` are [T, H, D] f32), growing the
        sequence's block table page by page.  All pages the write needs
        are allocated up front, so `KVCacheExhausted` leaves the sequence
        exactly as it was."""
        k = np.asarray(k, np.float32)
        v = np.asarray(v, np.float32)
        T = k.shape[0]
        with self._lock:
            seq = self._seqs[seq_id]
            have = len(seq.blocks) * self.page_size - seq.length
            need_pages = max(0, -(-(T - have) // self.page_size))
            if need_pages:
                seq.blocks.extend(self.allocator.alloc(need_pages))
            for t in range(T):
                pos = seq.length + t
                blk = seq.blocks[pos // self.page_size]
                slot = pos % self.page_size
                self._write_token(blk, slot, k[t], v[t])
            seq.length += T

    def _write_token(self, blk: int, slot: int, k_t: np.ndarray,
                     v_t: np.ndarray) -> None:
        if self.dtype == "int8":
            qk = quantize_tensor(k_t, axis=0)      # [H, D]: scale per head
            qv = quantize_tensor(v_t, axis=0)
            self.k_pages[blk, slot] = np.asarray(qk.q)
            self.v_pages[blk, slot] = np.asarray(qv.q)
            self.k_scales[blk, slot] = np.asarray(qk.scale).reshape(-1)
            self.v_scales[blk, slot] = np.asarray(qv.scale).reshape(-1)
        else:
            self.k_pages[blk, slot] = k_t
            self.v_pages[blk, slot] = v_t

    def free_seq(self, seq_id: int) -> None:
        with self._lock:
            seq = self._seqs.pop(seq_id, None)
        if seq is not None and seq.blocks:
            self.allocator.free(seq.blocks)

    def seq_len(self, seq_id: int) -> int:
        with self._lock:
            return self._seqs[seq_id].length

    # ---- attention inputs ----
    def block_tables(self, seq_ids: Sequence[int], rows: int,
                     max_pages: int) -> Tuple[np.ndarray, np.ndarray]:
        """[rows, max_pages] int32 block tables + [rows] int32 lengths
        for `seq_ids`, padded: unused table slots and padding rows hold
        block 0 / length 1 (masked garbage the caller discards)."""
        bt = np.zeros((rows, max_pages), np.int32)
        sl = np.ones(rows, np.int32)
        with self._lock:
            for i, sid in enumerate(seq_ids):
                seq = self._seqs[sid]
                bt[i, :len(seq.blocks)] = seq.blocks
                sl[i] = max(seq.length, 1)
        return bt, sl

    def pages(self) -> Tuple[np.ndarray, ...]:
        """The attention operands: (k_pages, v_pages) for f32 pages,
        plus (k_scales, v_scales) for int8 pages."""
        if self.dtype == "int8":
            return (self.k_pages, self.v_pages,
                    self.k_scales, self.v_scales)
        return (self.k_pages, self.v_pages)

    # ---- accounting ----
    @property
    def blocks_in_use(self) -> int:
        return self.allocator.in_use

    @property
    def bytes_per_block(self) -> int:
        kv = 2 * self.page_size * self.n_heads * self.head_dim
        if self.dtype == "int8":
            return kv + 2 * self.page_size * self.n_heads * 4  # f32 scales
        return kv * 4

    @property
    def bytes_in_use(self) -> int:
        return self.blocks_in_use * self.bytes_per_block

    @property
    def active_sequences(self) -> int:
        with self._lock:
            return len(self._seqs)


# ---------------------------------------------------------------------------
# A minimal decode model (tests / examples)
# ---------------------------------------------------------------------------


class TinyDecodeModel:
    """Smallest model implementing the decode contract: `prefill(tokens,
    lens)`, `decode_qkv(tokens)`, `decode_out(attn)` — an embedding, one
    causal-attention block's QKV/out projections, and a logits head, all
    jnp so the engine can jit it.  Prefill position t and a decode step
    at position t run the identical math (causal attention over 0..t),
    so generation is prefix-invariant: the spec the decode tests pin."""

    def __init__(self, vocab: int = 128, d_model: int = 64,
                 n_heads: int = 4, seed: int = 0):
        import jax.numpy as jnp
        if d_model % n_heads:
            raise ValueError("d_model must divide into heads")
        self.vocab = int(vocab)
        self.d_model = int(d_model)
        self.n_heads = int(n_heads)
        self.head_dim = d_model // n_heads
        rng = np.random.default_rng(seed)
        s = 1.0 / math.sqrt(d_model)

        def w(shape, scale):
            return jnp.asarray(
                rng.standard_normal(shape) * scale, jnp.float32)

        self.params_ = {
            "embed": w((vocab, d_model), 0.3),
            "wq": w((d_model, d_model), s),
            "wk": w((d_model, d_model), s),
            "wv": w((d_model, d_model), s),
            "wo": w((d_model, d_model), s),
            "head": w((d_model, vocab), s),
        }

    def _proj(self, x, name):
        import jax.numpy as jnp
        y = x @ self.params_[name]
        return y.reshape(x.shape[:-1] + (self.n_heads, self.head_dim))

    def prefill(self, tokens, lens):
        """[B, T] int32 prompts (zero-padded past `lens`) -> (last-token
        logits [B, V], k [B, T, H, D], v [B, T, H, D])."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops import attention_kernels as ak
        p = self.params_
        B, T = tokens.shape
        x = p["embed"][tokens]                       # [B, T, dm]
        q, k, v = (self._proj(x, n) for n in ("wq", "wk", "wv"))
        qh, kh, vh = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        keep = (jnp.arange(T)[None, :]
                < lens[:, None]).astype(jnp.float32)  # [B, T]
        o = ak.mha_reference(qh, kh, vh, mask=keep, causal=True)
        h = o.transpose(0, 2, 1, 3).reshape(B, T, self.d_model) @ p["wo"]
        logits = h @ p["head"]                       # [B, T, V]
        last = logits[jnp.arange(B), lens - 1]       # [B, V]
        return last, k, v

    def decode_qkv(self, tokens):
        """[B] int32 -> (q, k, v) each [B, H, D] for one decode step."""
        x = self.params_["embed"][tokens]            # [B, dm]
        return (self._proj(x, "wq"), self._proj(x, "wk"),
                self._proj(x, "wv"))

    def decode_out(self, attn):
        """[B, H, D] paged-attention output -> logits [B, V]."""
        p = self.params_
        h = attn.reshape(attn.shape[0], self.d_model) @ p["wo"]
        return h @ p["head"]


# ---------------------------------------------------------------------------
# Decode sequences
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)           # identity eq, like _Request
class DecodeSequence:
    seq_id: int
    prompt: np.ndarray                     # [T] int32
    max_new_tokens: int
    future: Future
    priority: int = 0
    eos_token: Optional[int] = None
    enqueued: float = 0.0                  # time.monotonic()
    deadline: Optional[float] = None       # absolute monotonic, or None
    generated: List[int] = dataclasses.field(default_factory=list)
    t_last: float = 0.0                    # last token emit (monotonic)
    restarts: int = 0                      # failover restarts (fleet)


def _paged_attn(q, k_pages, v_pages, block_tables, seq_lens,
                k_scales=None, v_scales=None):
    """Tier-dispatched paged attention (trace-time decision, like every
    other kernel call site): Pallas on accelerators / forced mode,
    reference on CPU auto — so tier-1 stays green."""
    impl = kd.resolve("paged_attention", q, k_pages, v_pages,
                      block_tables, seq_lens,
                      k_scales=k_scales, v_scales=v_scales)
    if impl == "pallas":
        return pa.paged_attention(
            q, k_pages, v_pages, block_tables, seq_lens,
            k_scales=k_scales, v_scales=v_scales,
            tile=kd.get_tile("paged_attention"),
            interpret=kd.interpret_mode())
    return pa.paged_attention_reference(
        q, k_pages, v_pages, block_tables, seq_lens,
        k_scales=k_scales, v_scales=v_scales)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class DecodeEngine:
    """Token-level continuous batching over a paged KV cache.

    `submit()` enqueues a prompt and returns a Future resolving to the
    generated token ids; one worker thread runs the admit → step → retire
    loop.  Program shapes are fully bucketed (prompt-length pow2 buckets
    for prefill, batch-row pow2 buckets for decode, a fixed pool shape
    for KV), so after `warmup()` a shape-skewed flood triggers zero fresh
    XLA compiles — verified via the jit caches themselves
    (`fresh_compiles()`)."""

    _ids = itertools.count()

    def __init__(self, model, *, num_blocks: int = 128,
                 page_size: Optional[int] = None, max_seq_len: int = 256,
                 max_decode_batch: int = 8, kv_dtype: str = "f32",
                 max_waiting: int = 64, max_new_tokens_default: int = 32,
                 prompt_min_bucket: int = 8,
                 model_label: str = "decode",
                 server_label: Optional[str] = None,
                 registry_: Optional[MetricsRegistry] = None):
        import jax
        self.model = model
        tile = kd.get_tile("paged_attention")
        self.page_size = int(page_size) if page_size else \
            max(int(tile.block_kv), 1)
        self.max_seq_len = int(max_seq_len)
        self.max_pages = -(-self.max_seq_len // self.page_size)
        self.max_decode_batch = int(max_decode_batch)
        self.max_waiting = int(max_waiting)
        self.max_new_tokens_default = int(max_new_tokens_default)
        self.kv_dtype = kv_dtype
        self.model_label = model_label
        kd.set_kv_dtype(kv_dtype)     # f32-KV vs int8-KV programs must
        #                               never share an AOT cache entry
        self.cache = PagedKVCache(num_blocks, self.page_size,
                                  model.n_heads, model.head_dim,
                                  dtype=kv_dtype)
        self.metrics = ServingMetrics(
            server_label=server_label if server_label is not None
            else f"decode{next(DecodeEngine._ids)}",
            model_label=model_label, registry_=registry_)
        self.instruments = decode_instruments() if registry_ is None \
            else DecodeInstruments(registry_)
        self.compile_counters = HitMissCounters("decode_compile")
        self._shapes: Set[Tuple] = set()
        # pow2 ladders: prompt buckets over the time axis, decode buckets
        # over batch rows — serving/compile_cache.py's ladder, reused
        max_prompt = max(self.max_seq_len - 1, 1)
        self.prompt_buckets = bucket_sizes(
            max_prompt, min_bucket=min(prompt_min_bucket, max_prompt))
        self.batch_buckets = bucket_sizes(self.max_decode_batch)
        self._prefill_jit = jax.jit(model.prefill)
        self._qkv_jit = jax.jit(model.decode_qkv)
        self._attn_jit = jax.jit(self._attn_step)
        self._waiting: List[DecodeSequence] = []
        self._active: List[DecodeSequence] = []
        self._cond = threading.Condition()
        self._stop = False
        self._draining = False
        self._poisoned: Optional[BaseException] = None
        self._step_since: Optional[float] = None
        self._seq_ids = itertools.count()
        self.tokens_emitted = 0
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name="decode-engine")
        self._worker.start()

    # ---- jitted step tail: paged attention + head ----
    def _attn_step(self, q, k_pages, v_pages, k_scales, v_scales,
                   block_tables, seq_lens):
        attn = _paged_attn(q, k_pages, v_pages, block_tables, seq_lens,
                           k_scales=k_scales, v_scales=v_scales)
        return self.model.decode_out(attn)

    # ---- compile accounting ----
    def _count_shape(self, kind: str, key) -> None:
        k = (kind, key)
        if k in self._shapes:
            self.compile_counters.hit()
            self.metrics.cache.hit()
        else:
            self._shapes.add(k)
            self.compile_counters.miss()
            self.metrics.cache.miss()

    def fresh_compiles(self) -> int:
        """Traced-program count across the engine's jit caches — the
        ground truth a zero-recompile check reads (shape-key
        accounting can lie; the jit cache cannot)."""
        total = 0
        for f in (self._prefill_jit, self._qkv_jit, self._attn_jit):
            try:
                total += f._cache_size()
            except Exception:       # fallback: our own shape accounting
                return int(self.compile_counters.misses.value)
        return total

    # ---- client side ----
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               priority: int = 0, deadline_ms: Optional[float] = None,
               eos_token: Optional[int] = None) -> Future:
        """Enqueue one prompt; the Future resolves to the generated token
        ids (np.int32, `<= max_new_tokens` of them — shorter on EOS).
        Raises `RejectedError` when shedding (queue full, prompt that can
        never fit, shutdown)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        mnt = int(max_new_tokens) if max_new_tokens is not None \
            else self.max_new_tokens_default
        if prompt.size + mnt > self.max_seq_len:
            raise RejectedError(
                f"prompt of {prompt.size} + {mnt} new tokens exceeds "
                f"max_seq_len={self.max_seq_len}")
        now = time.monotonic()
        seq = DecodeSequence(
            seq_id=next(self._seq_ids), prompt=prompt,
            max_new_tokens=mnt, future=Future(), priority=int(priority),
            eos_token=eos_token, enqueued=now,
            deadline=None if deadline_ms is None
            else now + float(deadline_ms) / 1000.0)
        with self._cond:
            if self._poisoned is not None:
                # fatal, not shed: the caller's failover should poison
                # this replica and restart the sequence elsewhere
                from deeplearning4j_tpu.serving.resilience import \
                    FatalReplicaError
                self.metrics.rejected.inc()
                raise FatalReplicaError(
                    f"decode engine poisoned: {self._poisoned!r}")
            if self._stop or self._draining:
                self.metrics.rejected.inc()
                self.metrics.record_shed(seq.priority, "rejected")
                raise RejectedError("decode engine is shut down")
            if len(self._waiting) >= self.max_waiting:
                self.metrics.rejected.inc()
                self.metrics.record_shed(seq.priority, "rejected")
                raise RejectedError(
                    f"decode queue full ({self.max_waiting} waiting); "
                    "load shed — back off and retry")
            self._waiting.append(seq)
            self.metrics.record_submit(
                len(self._waiting) + len(self._active))
            self._cond.notify_all()
        return seq.future

    def generate(self, prompt, **kw) -> np.ndarray:
        """Blocking convenience form of `submit`."""
        timeout = kw.pop("timeout", None)
        return self.submit(prompt, **kw).result(timeout=timeout)

    def cancel(self, fut: Future) -> bool:
        """Retire the sequence behind `fut` NOW — waiting or mid-flight.
        Its queue slot and KV pages are released immediately (the
        batcher-cancel semantics at token granularity)."""
        with self._cond:
            for seq in self._waiting:
                if seq.future is fut:
                    self._waiting.remove(seq)
                    self._cond.notify_all()
                    fut.cancel()
                    return True
            for seq in self._active:
                if seq.future is fut:
                    self._retire_locked(seq)
                    fut.cancel()
                    return True
        return False

    # ---- probes / stats ----
    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._waiting) + len(self._active)

    @property
    def accepting(self) -> bool:
        with self._cond:
            return not (self._stop or self._draining
                        or self._poisoned is not None)

    @property
    def step_age_s(self) -> Optional[float]:
        since = self._step_since
        return None if since is None else time.monotonic() - since

    def readyz(self) -> Dict[str, Any]:
        reasons = []
        if self._poisoned is not None:
            reasons.append(f"engine poisoned: {self._poisoned!r}")
        if self._stop or self._draining:
            reasons.append("engine is shut down")
        return {"ready": not reasons, "reasons": reasons}

    def stats(self) -> Dict[str, Any]:
        with self._cond:
            active, waiting = len(self._active), len(self._waiting)
        return {
            "active": active, "waiting": waiting,
            "tokens_emitted": self.tokens_emitted,
            "kv": {"dtype": self.kv_dtype,
                   "page_size": self.page_size,
                   "blocks_in_use": self.cache.blocks_in_use,
                   "blocks_total": self.cache.allocator.num_blocks,
                   "bytes_in_use": self.cache.bytes_in_use,
                   "high_water": self.cache.allocator.high_water},
            "compile": dict(self.compile_counters.snapshot(),
                            fresh=self.fresh_compiles()),
            "buckets": {"prompt": list(self.prompt_buckets),
                        "batch": list(self.batch_buckets)},
        }

    # ---- warmup ----
    def warmup(self) -> int:
        """Compile every prefill prompt bucket and decode batch bucket
        ahead of traffic; returns the number of traced programs.  After
        this, any admissible flood runs with zero fresh compiles."""
        import jax.numpy as jnp
        lens = jnp.ones(1, jnp.int32)
        for tb in self.prompt_buckets:
            self._count_shape("prefill", tb)
            self._prefill_jit(jnp.zeros((1, tb), jnp.int32), lens)
        pages = tuple(np.asarray(p) for p in self.cache.pages())
        if self.kv_dtype != "int8":
            pages = pages + (None, None)
        for bb in self.batch_buckets:
            self._count_shape("decode", bb)
            q, _, _ = self._qkv_jit(jnp.zeros(bb, jnp.int32))
            self._attn_jit(q, *pages,
                           jnp.zeros((bb, self.max_pages), jnp.int32),
                           jnp.ones(bb, jnp.int32))
        return self.fresh_compiles()

    # ---- worker: admit / prefill ----
    def _admit_locked(self) -> None:
        """Move waiting sequences into the decode batch (priority order,
        FIFO within a level) while batch slots AND KV pages allow; a
        pool-exhausted admit stops cleanly — the sequence stays queued
        and retries next step, after retirements free pages."""
        now = time.monotonic()
        for seq in list(self._waiting):
            if seq.future.cancelled():
                self._waiting.remove(seq)
            elif seq.deadline is not None and now > seq.deadline:
                self._waiting.remove(seq)
                self.metrics.expired.inc()
                self.metrics.record_shed(seq.priority, "expired")
                seq.future.set_exception(DeadlineExceededError(
                    "deadline passed before prefill"))
        self._waiting.sort(key=lambda s: (-s.priority, s.enqueued))
        for seq in list(self._waiting):
            if len(self._active) >= self.max_decode_batch:
                break
            try:
                self._prefill(seq)
            except KVCacheExhausted:
                break                    # no pages now; retry next step
            except Exception as e:       # model failure: fail this seq
                self._waiting.remove(seq)
                self.metrics.failed.inc()
                if not seq.future.cancelled():
                    seq.future.set_exception(e)
                continue
            self._waiting.remove(seq)
        self._note_gauges()

    def _prefill(self, seq: DecodeSequence) -> None:
        """One sequence through the bucketed prefill: pad the prompt to
        its pow2 bucket, trace-once-per-bucket, write prompt KV into
        fresh pages, and emit the first generated token."""
        import jax.numpy as jnp
        T = int(seq.prompt.size)
        tb = bucket_for(T, self.prompt_buckets[-1],
                        min_bucket=self.prompt_buckets[0])
        self._count_shape("prefill", tb)
        tokens = np.zeros((1, tb), np.int32)
        tokens[0, :T] = seq.prompt
        last, k, v = self._prefill_jit(jnp.asarray(tokens),
                                       jnp.asarray([T], jnp.int32))
        self.cache.allocate(seq.seq_id)
        try:
            self.cache.write(seq.seq_id, np.asarray(k)[0, :T],
                             np.asarray(v)[0, :T])
        except KVCacheExhausted:
            self.cache.free_seq(seq.seq_id)
            raise
        self._active.append(seq)
        now = time.monotonic()
        seq.t_last = now
        tok = int(np.argmax(np.asarray(last)[0]))
        self._emit(seq, tok, inter_ms=None, now=now)

    # ---- worker: one decode step ----
    def _emit(self, seq: DecodeSequence, tok: int,
              inter_ms: Optional[float], now: float) -> None:
        seq.generated.append(tok)
        self.tokens_emitted += 1
        self.instruments.record_token(self.model_label, inter_ms)
        done = (len(seq.generated) >= seq.max_new_tokens
                or (seq.eos_token is not None and tok == seq.eos_token))
        expired = (seq.deadline is not None and now > seq.deadline)
        if done:
            self._retire_locked(seq)
            self.metrics.completed.inc()
            self.metrics.record_latency((now - seq.enqueued) * 1000.0)
            if not seq.future.cancelled():
                seq.future.set_result(
                    np.asarray(seq.generated, np.int32))
        elif expired:
            self._retire_locked(seq)
            self.metrics.expired.inc()
            self.metrics.record_shed(seq.priority, "expired")
            if not seq.future.cancelled():
                seq.future.set_exception(DeadlineExceededError(
                    f"deadline passed after {len(seq.generated)} tokens"))

    def _retire_locked(self, seq: DecodeSequence) -> None:
        """Drop a sequence from the decode batch and release its KV pages
        + batch slot IMMEDIATELY (mid-group, between steps) — the next
        `_admit_locked` can use them, no group-boundary settling."""
        if seq in self._active:
            self._active.remove(seq)
        try:
            self.cache.free_seq(seq.seq_id)
        except KeyError:
            pass
        self._cond.notify_all()

    def _step_locked(self) -> None:
        """Advance every active sequence one token: batched QKV at the
        pow2 row bucket, host-append of the new KV rows (a page alloc on
        page boundaries — exhaustion sheds that one sequence), then the
        paged-attention + head program, then sample/emit/retire."""
        import jax.numpy as jnp
        actives = list(self._active)
        B = len(actives)
        bb = bucket_for(B, self.batch_buckets[-1],
                        min_bucket=self.batch_buckets[0])
        self._step_since = time.monotonic()
        try:
            tokens = np.zeros(bb, np.int32)
            for i, seq in enumerate(actives):
                tokens[i] = seq.generated[-1]
            self._count_shape("decode", bb)
            q, k, v = self._qkv_jit(jnp.asarray(tokens))
            k = np.asarray(k)
            v = np.asarray(v)
            live: List[Tuple[int, DecodeSequence]] = []
            for i, seq in enumerate(actives):
                if seq.future.cancelled():
                    self._retire_locked(seq)
                    continue
                try:
                    self.cache.write(seq.seq_id, k[i:i + 1], v[i:i + 1])
                except KVCacheExhausted as e:
                    self._retire_locked(seq)   # shed THIS sequence only
                    self.metrics.record_shed(seq.priority, "rejected")
                    self.metrics.rejected.inc()
                    if not seq.future.cancelled():
                        seq.future.set_exception(e)
                    continue
                live.append((i, seq))
            if not live:
                return
            bt, sl = self.cache.block_tables(
                [s.seq_id for _, s in live], bb, self.max_pages)
            # scatter lengths back to each sequence's original row; rows
            # of retired/padding sequences keep (block 0, length 1)
            bt_full = np.zeros((bb, self.max_pages), np.int32)
            sl_full = np.ones(bb, np.int32)
            for j, (i, _) in enumerate(live):
                bt_full[i] = bt[j]
                sl_full[i] = sl[j]
            pages = tuple(np.asarray(p) for p in self.cache.pages())
            if self.kv_dtype != "int8":
                pages = pages + (None, None)
            logits = np.asarray(self._attn_jit(
                q, *pages, jnp.asarray(bt_full), jnp.asarray(sl_full)))
            now = time.monotonic()
            self.metrics.record_dispatch(
                n_requests=0, rows=len(live), padded_rows=bb - len(live),
                dispatch_ms=(now - self._step_since) * 1000.0)
            for i, seq in live:
                tok = int(np.argmax(logits[i]))
                inter = (now - seq.t_last) * 1000.0
                seq.t_last = now
                self._emit(seq, tok, inter_ms=inter, now=now)
        finally:
            self._step_since = None
            self._note_gauges()

    def _note_gauges(self) -> None:
        self.instruments.record_active(self.model_label,
                                       len(self._active))
        self.instruments.record_kv(
            self.model_label, self.cache.blocks_in_use,
            self.cache.bytes_in_use, self.kv_dtype)
        self.metrics.record_queue_depth(
            len(self._waiting) + len(self._active))

    # ---- worker loop ----
    def _loop(self) -> None:
        while True:
            with self._cond:
                if self._stop:
                    return
                self._admit_locked()
                if not self._active:
                    if self._draining and not self._waiting:
                        return
                    self._cond.wait(timeout=0.02)
                    continue
                try:
                    self._step_locked()
                except Exception as e:   # device path died: poison
                    self._poison_locked(e)
                    return

    def _poison_locked(self, exc: BaseException) -> None:
        self._poisoned = exc
        for seq in self._active + self._waiting:
            try:
                self.cache.free_seq(seq.seq_id)
            except KeyError:
                pass
            self.metrics.failed.inc()
            if not seq.future.done():
                seq.future.set_exception(exc)
        self._active.clear()
        self._waiting.clear()
        self._cond.notify_all()

    # ---- failure / lifecycle ----
    def kill(self, exc: Optional[BaseException] = None) -> None:
        """Fail the engine NOW (chaos hook / replica-death injection):
        every in-flight and waiting sequence fails with a fatal replica
        error — the fleet's failover restarts them elsewhere, counted."""
        from deeplearning4j_tpu.serving.resilience import FatalReplicaError
        e = exc if exc is not None else FatalReplicaError(
            "decode engine killed")
        with self._cond:
            self._poison_locked(e)
            self._stop = True
            self._cond.notify_all()

    @property
    def poisoned(self) -> Optional[BaseException]:
        return self._poisoned

    def shutdown(self, drain: bool = True, timeout: float = 10.0) -> None:
        """Stop admission; with `drain`, let the worker finish queued and
        in-flight sequences (bounded by `timeout`), then fail leftovers.
        Idempotent."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        if drain:
            end = time.monotonic() + timeout
            with self._cond:
                while ((self._waiting or self._active)
                       and self._poisoned is None
                       and time.monotonic() < end):
                    self._cond.wait(timeout=0.05)
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._worker.join(timeout=timeout)
        with self._cond:
            leftovers = self._active + self._waiting
            self._active, self._waiting = [], []
        for seq in leftovers:
            try:
                self.cache.free_seq(seq.seq_id)
            except KeyError:
                pass
            if not seq.future.done():
                seq.future.set_exception(RejectedError(
                    "decode engine shut down before this sequence "
                    "finished"))


# ---------------------------------------------------------------------------
# Fleet adapter: a DecodeEngine quacking like a ModelServer
# ---------------------------------------------------------------------------


class _EngineBatcherView:
    """The `server.batcher` surface the fleet machinery reads."""

    def __init__(self, engine: DecodeEngine):
        self._engine = engine

    @property
    def queue_depth(self) -> int:
        return self._engine.queue_depth

    @property
    def accepting(self) -> bool:
        return self._engine.accepting

    @property
    def inflight_age_s(self) -> Optional[float]:
        return self._engine.step_age_s


class _EngineCacheView:
    """The `server.cache` surface (drain/evict call `invalidate`)."""

    def invalidate(self) -> int:
        return 0


class DecodeServerAdapter:
    """Wraps a `DecodeEngine` in the exact ModelServer surface `Replica`
    / `FleetRouter` / `drain_replicas` touch (`batcher.queue_depth`,
    `cache.invalidate`, `readyz`, `shutdown`), so decode members ride the
    PR-12 failover machinery without a parallel code path."""

    def __init__(self, engine: DecodeEngine):
        self.engine = engine
        self.batcher = _EngineBatcherView(engine)
        self.cache = _EngineCacheView()

    @property
    def metrics(self) -> ServingMetrics:
        return self.engine.metrics

    def readyz(self) -> Dict[str, Any]:
        return self.engine.readyz()

    def healthz(self) -> Dict[str, Any]:
        return {"ok": self.engine.poisoned is None,
                "stats": self.engine.stats()}

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def shutdown(self, drain: bool = True, timeout: float = 10.0) -> None:
        self.engine.shutdown(drain=drain, timeout=timeout)
