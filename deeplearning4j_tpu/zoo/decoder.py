"""A decoder-only language model over a per-layer list of token mixers,
with dense or sparse-expert feed-forward layers, on the train path.

Pre-norm residual blocks `h = x + Op_l(RMSNorm(x))`, `y = h + F_l(RMSNorm(h))`
(RMSNorm in float32, `ops/norm_kernels.rms_norm`), no position embedding,
next-token cross-entropy under a causal mask or (`objective`) the
block-diffusion loss below.  `DecoderConfig.layer_types` names `Op_l` layer
by layer, or `layer_pattern` makes each layer ONE of the two sub-blocks
(below); six published models are the presets the tests and the benchmark
build:

- `deepseek_v3` configs (DeepSeek-V2/V3, arXiv:2405.04434, arXiv:2412.19437;
  kanana-2-30b-a3b): `latent_attention` in every layer (the default list) —
  multi-head latent attention in its expanded form: queries of `qk_nope +
  qk_rope` dims, keys rebuilt from a `kv_lora_rank`-wide latent plus one
  rotary head shared by all (interleaved rotary), values of `v_head` dims,
  so keys are wider than values; shared experts beside the routed ones; an
  untied head.
- `lfm2_moe` configs (LFM2-24B-A2B): `conv` layers — the gated short
  convolution of `ops/short_conv.py`, linear in the sequence — beside
  `full_attention` layers — grouped-query heads (`n_heads` queries over
  `n_kv_heads` keys and values of `head_dim`), RMSNorm of every query and
  key head, half-split rotary — in a repeating pattern; no shared expert;
  the head is the embedding's transpose (`tie_embeddings`).
- `sdar_moe` configs (SDAR-30B-A3B: a Qwen3-MoE block trained by block
  diffusion): `full_attention` in every layer and NO dense layer; a softmax
  router (`router_score`) balanced by an auxiliary loss (`aux_loss_coef`);
  `objective="block_diffusion"` (BD3-LM, arXiv:2503.09573; SDAR,
  arXiv:2510.06303): the step draws, for each block of `block_length`
  tokens, `t ~ U(noise_eps, 1)` and replaces each of its tokens by
  `mask_token_id` with probability `t`; the model runs on the 2L rows
  `[noisy ; clean]`, both halves at positions `0 .. L-1`, under
  `fused_attention(block_diffusion=(L, block_length))` — a noisy row sees
  its own noisy block and the clean blocks before it, a clean row the clean
  blocks up to its own — and the loss is the cross-entropy of the noisy
  half's logits against the clean token AT THE SAME position (no shift),
  over the replaced positions, each weighted `1/t`, over L.  The noise
  comes from `fold_in(PRNGKey(seed), iteration)` on the device, so
  `fit(iterator)` consumes `[ids]` as ever.  Without a noisy copy
  (`output(ids)`) the same mask is attention causal over blocks: the
  forward a block-wise denoising decode would run.

- `KeyeVL2` configs (Keye-VL-2.0-30B-A3B's language model: the same
  Qwen3-MoE block, trained on the next token): `sparse_attention` in every
  layer — `full_attention`'s heads, of which every query attends only the
  `index_topk` keys that the layer's INDEXER scores highest (DeepSeek-V3.2's
  lightning indexer, `ops/sparse_index.py`: `index_heads` small heads over
  one key head, ReLU, a weighted sum, an exact top-k a query); rotary
  frequencies driven by several position streams in sections
  (`rope_sections`; text gives all streams the token's index).  The
  selection is DATA: it reaches the flash kernels as an operand, one bit a
  pair.  The indexer reads the block's normed input under `stop_gradient`
  and is trained by a loss of its own, the KL of its softmax over a
  query's selected keys from the main heads' mean probabilities there
  (`index_loss_coef` times its mean over the layers is the loss's third
  term); nothing else learns from that term and nothing differentiates
  through the choice of keys.  Counted on the device: `selected_keys`,
  `index_kl` and `tie_split_chunks` (`sparse_stats()`).

- `solar_open2` configs (Solar Open 2: Kimi delta attention beside gated
  NoPE attention): `linear_attention` in 3 of every 4 layers — a gated
  delta rule with a decay a channel (`ops/linear_attention.py`: q, k, v
  through a causal depthwise convolution and SiLU, q and k L2-normed, a
  low-rank log decay `-exp(A_log) softplus(h W_fa W_fb + dt_bias)`, beta in
  (0, 2) (negative eigenvalues, as Solar allows), the state [dk, dv] a head
  carried chunk to chunk by a Mosaic kernel, a gated RMSNorm a head) —
  beside `full_attention` with no rotary, no norm of queries and keys and
  an elementwise sigmoid gate on the heads' output (`rope`, `qk_norm`,
  `attn_output_gate`); a sigmoid router beside one shared expert in every
  layer; an untied head.  Counted on the device: `delta_rule_updates`
  (`linear_stats()`).

- `nemotron_h` configs (Nemotron-H, arXiv:2504.03624: the tower of
  Nemotron-Labs-TwoTower-30B-A3B that its config describes):
  `layer_pattern` is the `hybrid_override_pattern` of the layers held, one
  character a layer and each layer one residual sub-block, `x + Op(RMSNorm
  x)`: `M` a `mamba2` mixer, `*` a `full_attention` mixer (here with no
  rotary, no norm of queries and keys, no gate), `E` an expert layer alone
  (`EXPERTS`).  The Mamba-2 mixer (arXiv:2405.21060): `[z | xBC | dt] = h
  W_in` as three products; `xBC = SiLU(conv(xBC) + b)` (causal, depthwise,
  `conv_kernel` taps, a bias); `x | B | C` as `ssm_heads` heads of
  `ssm_head_dim` and `ssm_groups` groups of `ssm_state` (a head reads group
  `head // (heads / groups)`); `dt = softplus(dt + dt_bias)`, `A =
  -exp(A_log)` a head; the state-space dual `ops/state_space.ssd` in chunks
  of `ssm_chunk` with a `D` skip; `RMSNorm_grouped(y * SiLU(z))` over
  `ssm_groups` groups (`ops/norm_kernels.gated_rms_norm`); `W_out`.  Its
  experts, routed and shared, are squared-ReLU MLPs, not gated
  (`expert_act="relu2"`, `ops/moe.relu2_mlp`), the shared one
  `shared_intermediate` wide; the sigmoid router with a selection bias; an
  untied head.  The router bias and the routing counters have one row an
  `E` layer.

Between a product and a kernel (latent attention; measured in PERF.md,
PR 36).  The kernels read [B, heads, T, d] with `d` in the lanes; a product
`x @ W` writes [B, T, heads * d]; every slice, concatenate or transpose of a
100 MB array between the two is a pass over it.  So each piece is a product
of its own.  `v`: `Wkvb`'s value columns, an `einsum` to `btnd` and a
transpose, which XLA's TPU layout assignment turns into a product that writes
[B, heads, T, 128] itself (it does not for `->bntd`, nor for any 192-wide
result: those it lays out with the tokens in the lanes and copies once).
q: three products — `Wq`'s nope columns, its rotary pairs' first members,
their second (the rotary columns taken apart by a 0/1 matrix) — so
`ops/rotary.rotary_pairs` turns two whole arrays in the products' epilogue
and no lane changes place; k likewise from `Wkvb`'s key columns and the one
rotary head of `Wkva`; one concatenate each for q and k, which XLA follows
with one transposing copy.  The kernel's output enters `Wo` as it stands (a
transpose and a reshape before a product that contracts over both are no
copy).  The weights are taken apart inside the block, in the compute dtype,
and autodiff puts their gradients back together: the parameter tree and a
checkpoint know nothing of it.

All of them through `ops/attention_kernels.fused_attention` (the flash
kernels from 2k tokens on the chip: forward and backward walk the mask's
live tiles by `tile_schedule`, so neither causal's upper triangle nor the
block mask's empty quadrant and off-diagonal costs a grid step, and the
backward's spans of queries write dK/dV for the key blocks they see alone).
`F_l` is a SwiGLU for the first
`n_dense_layers` layers (there may be none) and `ops/moe.expert_layer`
after: a sigmoid router over `n_experts` with a selection bias that the step
updates (no auxiliary loss), or the softmax router; top-k.

`first_expert`/`n_experts_held` say which routed experts this process
holds of each layer (all of them by default), and `first_head` /
`n_heads_held` which heads of a `full_attention` or `linear_attention`
layer (query heads, with the key-value heads they share, whole): the layer
computes its heads' part of `W_o`'s product and the partial sum goes on,
as the experts' does.  Held alone, the layer
computes its experts' part of the result and the partial sum goes on — the
share one chip runs under expert parallelism, less the exchange; the routed
part's buffers are then twice the chip's even share of the (token, expert)
pairs long, not all pairs (`ops/moe.row_bound`), and `routed_rows()` counts
the steps whose routing needed a further pass over them.  The
vocabulary may be a slice likewise: `vocab_size` rows of embedding and head,
ids, logits and loss over the slice.

TPU-native choices, as `zoo/bert.py`: one jitted, donated train step;
float32 master parameters cast to `compute_dtype` a layer at a time.  The
expert layers are cut into whole PERIODS of their type list (`a c c c` x 9
and a remainder `a c` for the published LFM2-24B-A2B; a period of one layer
where all are alike) and the periods STACKED `[n, ...]` under one
`lax.scan`, so compile time is flat in depth; inside a period the layers are
unrolled, each with its own stacked parameters (an inner scan over three
like layers compiled slower for the chip, 43.6 s against 25-29 s, a real
loop inside a scan of one trip: PERF.md, PR 31); what is left of the list
after the last
whole period is unrolled after the scan.  The leading dense layers are of
one kind, stacked and unrolled.  What is saved for the backward pass is
fixed here, by measurement (PERF.md, PR 27 and PR 28): each block's input
and the attention kernel's output and logsumexp (of a `sparse_attention`
block also the selection's bits and the indexer's gradients, which its loss
takes in the forward pass: one top-k and one pass of that loss a layer a
step).  The rest of the block is
computed again in the backward pass, the flash forward kernel is not: its
two results are what its backward kernel needs and 68 MB a layer at 2 x
4,096 tokens, where q, k and v are 268 MB and come back from the
projections.  At 8,192 tokens a step beside 9.2 GB of training state saving
those too does not fit a 16 GB chip.  Where `fused_attention` takes no
Pallas kernel (the CPU, short sequences) there is nothing of that name and
the block is recomputed whole; a `conv` or a `linear_attention` block
has no such result and always
is.

Named scopes mark each part's device ops, forward and backward:
`mamba_mixer` and beneath it `ssd_scan` (the chunks and the state across
them), `mla_attention`, `gqa_attention`, `sparse_index` (the indexer's
projections, scores, top-k and the packed selection) and `index_loss`
beside it,
`linear_attention` and beneath it `delta_rule` (the chunks' insides and the
recurrence across them),
`short_conv` (beneath it `in_proj`, `mix`,
`out_proj`), `dense_mlp`, `moe` (`ops/moe.py`), `lm_head`, and for the
diffusion objective `bd_noise` (the draws, the replaced ids, the 2L input)
and `diffusion_loss` (the weighted cross-entropy and the auxiliary term).

Not here yet: prefill/decode through a cache (growing pages for the
attention layers beside a fixed `conv_kernel - 1` positions of state for the
convolutions, a [dk, dv] state a head for the delta rule and an [N, P] state
a head for the SSD, and a cache of
the indexer's keys with the selection inside the paged kernel), absorbed latent attention, the experts' exchange over
several chips, the decode loop that denoises a block over several passes, a
vision tower and with it position streams that differ, the indexer's dense
warm-up stage (the main model frozen), packed documents whose edges reset the
SSD's state, a second tower conditioned on this one (Nemotron-Labs-TwoTower's
denoiser).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.monitor.spans import note, note_step, span
from deeplearning4j_tpu.ops.attention_kernels import (FLASH_LSE, FLASH_OUT,
                                                      fused_attention,
                                                      unpack_selection)
from deeplearning4j_tpu.ops.linear_attention import (chunk_delta_rule,
                                                     l2_normalize)
from deeplearning4j_tpu.ops.moe import (expert_layer, row_bound, swiglu,
                                        update_router_bias)
from deeplearning4j_tpu.ops.norm_kernels import (gated_rms_norm,
                                                 layer_norm_reference,
                                                 rms_norm)
from deeplearning4j_tpu.ops.rotary import (rotary_half_split, rotary_pairs,
                                           rotary_sections)
from deeplearning4j_tpu.ops.short_conv import (causal_depthwise_conv,
                                               gated_short_conv)
from deeplearning4j_tpu.ops.sparse_index import (INDEX_GRADS, SELECTION,
                                                 index_loss, sparse_index)
from deeplearning4j_tpu.ops.state_space import ssd
from deeplearning4j_tpu.train.updaters import AdamW, IUpdater


LAYER_KINDS = ("latent_attention", "full_attention", "conv",
               "sparse_attention", "linear_attention", "mamba2")
# `layer_pattern`: a layer of experts alone
EXPERTS = "moe"
# `layer_pattern`'s characters (Nemotron-H's `hybrid_override_pattern`)
PATTERN = {"M": "mamba2", "*": "full_attention", "E": EXPERTS}


@dataclasses.dataclass
class DecoderConfig:
    vocab_size: int = 128256           # rows of embedding and head held here
    hidden: int = 2048
    n_layers: int = 48
    n_dense_layers: int = 1            # leading layers with a dense SwiGLU
    # each layer's token mixer, one of `LAYER_KINDS`; None: latent attention
    # in every layer
    layer_types: Optional[Sequence[str]] = None
    n_heads: int = 32
    n_kv_heads: int = 8                # `full_attention`: key-value heads
    head_dim: int = 64                 # and the width of all its heads
    conv_kernel: int = 3               # `conv`, `linear_attention`: taps of
                                       # the causal convolution
    qk_nope_dim: int = 128             # `latent_attention`, down to the rank
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate: int = 6144           # the dense layers' SwiGLU width
    expert_intermediate: int = 768
    n_experts: int = 128               # the router's width
    n_shared_experts: int = 2          # 0: a layer of routed experts alone
    top_k: int = 6
    routed_scale: float = 2.448
    router_eps: float = 1e-20          # added to the chosen scores' sum
    first_expert: int = 0              # the routed experts held here:
    n_experts_held: Optional[int] = None   # first .. first + held (None: all)
    rope_base: float = 1e6
    eps: float = 1e-6
    bias_update_speed: float = 1e-3
    init_std: float = 0.02             # every matrix but the embedding
    embedding_init_std: float = 1.0    # see `_init`
    tie_embeddings: bool = False       # the head is the embedding's transpose
    compute_dtype: str = "float32"     # "bfloat16" for TPU throughput
    router_score: str = "sigmoid"      # or "softmax": no bias, and
    aux_loss_coef: float = 0.0         # this much of `ops/moe.balance_loss`
    objective: str = "next_token"      # or "block_diffusion", with
    block_length: int = 4              # tokens that share a noise level,
    mask_token_id: Optional[int] = None    # the id a replaced token gets,
    noise_eps: float = 1e-3            # and t ~ U(noise_eps, 1) a block
    # `full_attention` and `sparse_attention`: the rotary frequencies each
    # of several position streams drives (None: one stream drives all)
    rope_sections: Optional[Sequence[int]] = None
    index_heads: int = 16              # `sparse_attention`: the indexer's
    index_head_dim: int = 64           # heads, over ONE key head of this width,
    index_topk: int = 2048             # the keys a query keeps,
    index_loss_coef: float = 1.0       # and this much of the indexer's loss
    # `full_attention`: rotary, an RMSNorm of every query and key head, and
    # an elementwise sigmoid gate on the heads' output (`W_g`)
    rope: bool = True
    qk_norm: bool = True
    attn_output_gate: bool = False
    # `full_attention` and `linear_attention`: the heads held here, first ..
    # first + held of `n_heads` (None: all), with their key-value heads
    first_head: int = 0
    n_heads_held: Optional[int] = None
    # one residual sub-block a layer, by `PATTERN`: "MEM*E" (None: every
    # layer a mixer and an FFN, `layer_types`)
    layer_pattern: Optional[str] = None
    ssm_heads: int = 64                # `mamba2`: heads of
    ssm_head_dim: int = 64             # this many channels,
    ssm_groups: int = 8                # groups of B and C
    ssm_state: int = 128               # of this width,
    ssm_chunk: int = 128               # tokens a chunk of the SSD,
    out_proj_init_std: Optional[float] = None   # and W_out's (None: init_std)
    expert_act: str = "swiglu"         # or "relu2": routed and shared
    shared_intermediate: Optional[int] = None   # a shared expert's width
                                                # (None: expert_intermediate)

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None \
            else self.n_experts_held

    @property
    def heads_held(self) -> Tuple[int, int]:
        """(query heads, key-value heads) held here."""
        n = self.n_heads if self.n_heads_held is None else self.n_heads_held
        return n, n * self.n_kv_heads // self.n_heads

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The token mixer of each of the `n_layers` layers; under
        `layer_pattern` the one sub-block of each, `EXPERTS` for experts."""
        if self.layer_pattern is not None:
            return tuple(PATTERN.get(ch, ch) for ch in self.layer_pattern)
        if self.layer_types is None:
            return ("latent_attention",) * self.n_layers
        return tuple(self.layer_types)

    def routes(self, kind: str) -> bool:
        """Whether a layer of `kind` has experts (a row of the router
        bias): every layer after the dense ones, under `layer_pattern` the
        `EXPERTS` layers alone."""
        return self.layer_pattern is None or kind == EXPERTS

    @property
    def n_expert_layers(self) -> int:
        return sum(map(self.routes, self.kinds[self.n_dense_layers:]))

    def layout(self):
        """`(dense kind, period, periods, rest)`: the expert layers' kinds
        are `period` repeated `periods` times and then `rest`, a proper
        start of one more period (empty where the list ends on a whole
        one); `period` is the shortest that does it."""
        kinds = self.kinds
        experts = kinds[self.n_dense_layers:]
        p = next(p for p in range(1, len(experts) + 1)
                 if all(k == experts[i % p] for i, k in enumerate(experts)))
        n = len(experts) // p
        return kinds[0], experts[:p], n, experts[n * p:]

    @staticmethod
    def tiny(**kw) -> "DecoderConfig":
        """Test-sized kanana-2 / DeepSeek-V3: latent attention in every
        layer, one dense and two expert layers, 8 experts top-2 and two
        shared, keys wider than values, untied head."""
        d = dict(vocab_size=96, hidden=32, n_layers=3, n_dense_layers=1,
                 n_heads=2, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
                 kv_lora_rank=16, intermediate=64, expert_intermediate=16,
                 n_experts=8, n_shared_experts=2, top_k=2)
        d.update(kw)
        return DecoderConfig(**d)

    @staticmethod
    def tiny_diffusion(**kw) -> "DecoderConfig":
        """Test-sized SDAR-MoE: two `full_attention` expert layers and no
        dense one, 4 query heads over 2 key-value heads of 8, a softmax
        router over 8 experts top-2 with an auxiliary loss, untied head,
        trained by block diffusion in blocks of 4 with the last id as the
        mask token."""
        d = dict(vocab_size=96, hidden=32, n_layers=2, n_dense_layers=0,
                 layer_types=("full_attention",) * 2, n_heads=4,
                 n_kv_heads=2, head_dim=8, expert_intermediate=16,
                 n_experts=8, n_shared_experts=0, top_k=2, routed_scale=1.0,
                 router_eps=0.0, router_score="softmax", aux_loss_coef=1e-3,
                 objective="block_diffusion", block_length=4,
                 mask_token_id=95)
        d.update(kw)
        return DecoderConfig(**d)

    @staticmethod
    def tiny_sparse(**kw) -> "DecoderConfig":
        """Test-sized Keye-VL-2.0's language model: two `sparse_attention`
        expert layers and no dense one — `tiny_diffusion`'s block trained on
        the next token, with an indexer of 2 heads of 8 beside each layer's
        4 / 2 heads of 8 that keeps 16 keys a query (so it binds from 32
        tokens on), rotary in sections [1, 1, 2] at 1e7."""
        d = dict(vocab_size=96, hidden=32, n_layers=2, n_dense_layers=0,
                 layer_types=("sparse_attention",) * 2, n_heads=4,
                 n_kv_heads=2, head_dim=8, expert_intermediate=16,
                 n_experts=8, n_shared_experts=0, top_k=2, routed_scale=1.0,
                 router_eps=0.0, router_score="softmax", aux_loss_coef=1e-3,
                 rope_base=1e7, rope_sections=(1, 1, 2), index_heads=2,
                 index_head_dim=8, index_topk=16)
        d.update(kw)
        return DecoderConfig(**d)

    @staticmethod
    def tiny_linear(**kw) -> "DecoderConfig":
        """Test-sized Solar Open 2: one period `full_attention,
        linear_attention x 3` of expert layers and no dense one; 4 query
        heads over 2 key-value heads of 8 with no rotary, no norm of queries
        and keys and an output gate; Kimi delta attention's 4 heads of 8
        with negative eigenvalues; a sigmoid router over 8 experts top-2
        beside one shared expert, scale 1; untied head."""
        d = dict(vocab_size=96, hidden=32, n_layers=4, n_dense_layers=0,
                 layer_types=("full_attention",) + ("linear_attention",) * 3,
                 n_heads=4, n_kv_heads=2, head_dim=8, expert_intermediate=16,
                 n_experts=8, n_shared_experts=1, top_k=2, routed_scale=1.0,
                 rope=False, qk_norm=False, attn_output_gate=True,
                 conv_kernel=4)
        d.update(kw)
        return DecoderConfig(**d)

    @staticmethod
    def tiny_mamba(**kw) -> "DecoderConfig":
        """Test-sized Nemotron-H: the pattern `MEM*E`, one sub-block a
        layer; Mamba-2's 4 heads of 8 over 2 groups of B and C of 16 in
        chunks of 8, conv 4; 4 query heads over 2 key-value heads of 8 with
        no rotary, no norm of queries and keys; 8 squared-ReLU experts top-2
        beside one shared expert of 32, scale 2.5; untied head."""
        d = dict(vocab_size=96, hidden=32, n_layers=5, n_dense_layers=0,
                 layer_pattern="MEM*E", n_heads=4, n_kv_heads=2, head_dim=8,
                 ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
                 ssm_chunk=8, conv_kernel=4, expert_intermediate=16,
                 shared_intermediate=32, expert_act="relu2", n_experts=8,
                 n_shared_experts=1, top_k=2, routed_scale=2.5, eps=1e-5,
                 rope=False, qk_norm=False)
        d.update(kw)
        return DecoderConfig(**d)

    @staticmethod
    def tiny_hybrid(**kw) -> "DecoderConfig":
        """Test-sized LFM2-MoE: a dense `conv` layer, then one period
        `full_attention, conv, conv, conv` of expert layers; 4 query heads
        over 2 key-value heads of 8, 8 experts top-2 with no shared expert,
        scale 1, epsilon 1e-6, tied head."""
        d = dict(vocab_size=96, hidden=32, n_layers=5, n_dense_layers=1,
                 layer_types=("conv", "full_attention", "conv", "conv",
                              "conv"),
                 n_heads=4, n_kv_heads=2, head_dim=8, conv_kernel=3,
                 intermediate=64, expert_intermediate=16, n_experts=8,
                 n_shared_experts=0, top_k=2, routed_scale=1.0,
                 router_eps=1e-6, eps=1e-5, tie_embeddings=True)
        d.update(kw)
        return DecoderConfig(**d)


def _key_stream(key):
    """Keys for `_init`, 32 to a split: a model that draws no more than 32
    (one kind of block) draws what `split(key, 32)` always gave it."""
    while True:
        yield from jax.random.split(key, 32)
        key = jax.random.fold_in(key, 32)


class DecoderModel:
    """LM over `DecoderConfig`.  `fit(iterator)` consumes MultiDataSets with
    features `[ids]` and labels `[next ids]`, both [B, T] int (the
    block-diffusion objective reads the ids alone); `output(ids)` returns
    the logits."""

    def __init__(self, config: DecoderConfig, seed: int = 0,
                 updater: Optional[IUpdater] = None):
        c = self.config = config
        if not 0 <= c.first_expert <= c.n_experts - c.held:
            raise ValueError(
                f"experts {c.first_expert}..{c.first_expert + c.held} are "
                f"not among the router's {c.n_experts}")
        kinds = c.kinds
        if c.layer_pattern is not None and (
                c.layer_types is not None or c.n_dense_layers
                or set(c.layer_pattern) - set(PATTERN)
                or EXPERTS not in kinds):
            raise ValueError(
                f"layer_pattern {c.layer_pattern!r}: one of {sorted(PATTERN)}"
                f" a layer, at least one E, no layer_types and no dense "
                f"layers")
        allowed = set(LAYER_KINDS) | ({EXPERTS} if c.layer_pattern else set())
        if len(kinds) != c.n_layers or set(kinds) - allowed:
            raise ValueError(
                f"layer_types names {len(kinds)} layers {sorted(set(kinds))}"
                f"; need {c.n_layers} of {LAYER_KINDS}")
        if c.expert_act not in ("swiglu", "relu2"):
            raise ValueError(f"expert_act {c.expert_act!r}")
        if "mamba2" in kinds and c.ssm_heads % c.ssm_groups:
            raise ValueError(f"{c.ssm_heads} SSM heads are no multiple of "
                             f"{c.ssm_groups} groups")
        if not 0 <= c.n_dense_layers < c.n_layers:
            raise ValueError("need at least one expert layer")
        if c.n_dense_layers and len(set(kinds[:c.n_dense_layers])) != 1:
            raise ValueError(
                f"the {c.n_dense_layers} leading dense layers are stacked: "
                f"they share one kind, not {kinds[:c.n_dense_layers]}")
        if {"full_attention", "sparse_attention"} & set(kinds) \
                and c.n_heads % c.n_kv_heads:
            raise ValueError(f"{c.n_heads} query heads are no multiple of "
                             f"{c.n_kv_heads} key-value heads")
        nh, nkv = c.heads_held
        if (c.first_head, nh) != (0, c.n_heads):
            group = c.n_heads // c.n_kv_heads
            if {"latent_attention", "sparse_attention", "mamba2"} \
                    & set(kinds) \
                    or not 0 <= c.first_head <= c.n_heads - nh \
                    or c.first_head % group or nh % group or nh < 1:
                raise ValueError(
                    f"heads {c.first_head}..{c.first_head + nh} of "
                    f"{c.n_heads}: a share is whole key-value heads of "
                    f"{group} query heads each, of full_attention and "
                    f"linear_attention layers")
        self._linear = "linear_attention" in kinds
        if self._linear and "linear_attention" in kinds[:c.n_dense_layers]:
            raise ValueError("a linear_attention layer counts its updates "
                             "with the expert layers': it goes after the "
                             "dense layers")
        if c.router_score not in ("sigmoid", "softmax"):
            raise ValueError(f"router_score {c.router_score!r}")
        if c.objective not in ("next_token", "block_diffusion"):
            raise ValueError(f"objective {c.objective!r}")
        self._diffusion = c.objective == "block_diffusion"
        self._sparse = "sparse_attention" in kinds
        if self._sparse and (self._diffusion or "sparse_attention"
                             in kinds[:c.n_dense_layers]):
            raise ValueError(
                "a sparse_attention layer selects among causal keys and "
                "counts its loss with the expert layers': it goes with the "
                "next_token objective, after the dense layers")
        if self._diffusion:
            if {"conv", "linear_attention", "mamba2"} & set(kinds):
                raise ValueError(
                    "a convolution or a recurrence would run across the "
                    "noisy and the clean copy of a sequence: block diffusion "
                    "takes softmax attention layers")
            if c.mask_token_id is None \
                    or not 0 <= c.mask_token_id < c.vocab_size:
                raise ValueError(f"mask_token_id {c.mask_token_id} is not "
                                 f"among the {c.vocab_size} ids held")
        self.seed = int(seed)       # of the parameters and of the noise
        self.updater = updater or AdamW(2.2e-4, weight_decay=0.1)
        self.iteration = 0
        self.epoch = 0
        # one program each, not one a leaf: a cold start compiles two
        self.params_ = jax.jit(self._init)(jax.random.PRNGKey(seed))
        self.opt_state_ = jax.jit(self.updater.init_state)(self.params_)
        n_moe = c.n_expert_layers
        # what the step carries beside the parameters: the routers'
        # selection bias (a buffer, no gradient) and, per expert layer since
        # the model was built, how many tokens chose each expert and in how
        # many steps the held pairs were more than the routed rows' bound
        self.state_ = {
            "router_bias": jnp.zeros((n_moe, c.n_experts), jnp.float32),
            "expert_load": jnp.zeros((n_moe, c.n_experts), jnp.int32),
            "rows_over_bound": jnp.zeros((n_moe,), jnp.int32)}
        if self._diffusion:         # positions that carried loss, all steps
            self.state_["masked_positions"] = jnp.zeros((), jnp.int32)
        if self._sparse:
            # pairs the indexer kept, a layer (float32: 31.5M a layer a step
            # at 16,384 tokens pass int32 in 68 steps), the steps' sum of
            # its mean loss, and the chunks of queries whose selection had
            # ties to split (`ops/sparse_index.py`: the searching branch)
            self.state_["selected_keys"] = jnp.zeros((n_moe,), jnp.float32)
            self.state_["index_kl"] = jnp.zeros((), jnp.float32)
            self.state_["tie_split_chunks"] = jnp.zeros((n_moe,), jnp.int32)
        if self._linear:
            # (token, held head) pairs whose state update ran a layer
            # (float32: 98,304 a layer a step at 4,096 tokens and 8 heads)
            self.state_["delta_rule_updates"] = jnp.zeros((n_moe,),
                                                          jnp.float32)
        self._tokens = 0     # clean tokens of the newest step
        self._pairs = 0      # (token, chosen expert) pairs of the newest step
        self._steps: Dict[str, Any] = {}
        self._unnoted_step = False    # a step built and not yet run

    # ---- init ----
    def _init(self, key) -> Dict[str, Any]:
        """Matrices normal(0, `init_std`), RMSNorm gains one.  The embedding
        rows are normal(0, `embedding_init_std`), 1 by default: under
        pre-norm the residual stream then carries the token, and a router
        sees it.  At 0.02 the blocks' outputs — mostly a component all
        positions share — drown it, every token of a batch picks the same
        experts in every layer, and an expert's load is all or nothing
        (measured, PERF.md PR 27): the first steps of a run, before the
        selection bias has done its work, not the routing by token that
        trained experts show (OpenMoE, arXiv:2402.01739, section 4).  A
        tied head is the same matrix: its logits have the root mean square
        `embedding_init_std * sqrt(hidden)`, so a tied model states a scale
        that serves both (benchmark/configs/lfm2_24b_a2b.json, `assumed`)."""
        c = self.config
        H, nh, hd = c.hidden, c.n_heads, c.head_dim
        nq, nkv = c.heads_held
        keys = _key_stream(key)

        def nrm(*shape, std=c.init_std):
            return (jax.random.normal(next(keys), shape) * std
                    ).astype(jnp.float32)

        def operator(kind, L):
            norms = {"norm1": jnp.ones((L, H)), "norm2": jnp.ones((L, H))}
            if kind == "latent_attention":
                return {
                    **norms,
                    "Wq": nrm(L, H, nh * (c.qk_nope_dim + c.qk_rope_dim)),
                    "Wkva": nrm(L, H, c.kv_lora_rank + c.qk_rope_dim),
                    "kv_norm": jnp.ones((L, c.kv_lora_rank)),
                    "Wkvb": nrm(L, c.kv_lora_rank,
                                nh * (c.qk_nope_dim + c.v_head_dim)),
                    "Wo": nrm(L, nh * c.v_head_dim, H)}
            if kind in ("full_attention", "sparse_attention"):
                # queries, keys and values side by side: one product
                p = {
                    **norms,
                    "Wqkv": nrm(L, H, (nq + 2 * nkv) * hd),
                    **({"q_norm": jnp.ones((L, hd)),
                        "k_norm": jnp.ones((L, hd))} if c.qk_norm else {}),
                    "Wo": nrm(L, nq * hd, H)}
                if c.attn_output_gate:
                    p["Wg"] = nrm(L, H, nq * hd)
                if kind == "sparse_attention":
                    # the indexer: its heads' queries, the one key head under
                    # a LayerNorm, a weight a head
                    n, d = c.index_heads, c.index_head_dim
                    p.update(Wq_idx=nrm(L, H, n * d), Wk_idx=nrm(L, H, d),
                             k_idx_gain=jnp.ones((L, d)),
                             k_idx_bias=jnp.zeros((L, d)),
                             Ww_idx=nrm(L, H, n))
                return p
            if kind == "linear_attention":
                # Kimi delta attention (FLA's KDA init): q, k, v side by
                # side and their convolution's taps (the variance of
                # PyTorch's Conv1d default at a fan-in of `taps`); the low-rank
                # decay and output gate; A = U(1, 16); dt_bias the inverse
                # softplus of dt = logU(1e-3, 1e-1)
                W, taps = nq * hd, c.conv_kernel
                lo, hi = np.log(1e-3), np.log(1e-1)
                dt = jnp.maximum(jnp.exp(jax.random.uniform(
                    next(keys), (L, W), jnp.float32, lo, hi)), 1e-4)
                return {
                    **norms,
                    "Wqkv": nrm(L, H, 3 * W),
                    "conv_qkv": nrm(L, taps, 3 * W, std=(3 * taps) ** -0.5),
                    "Wf_a": nrm(L, H, hd), "Wf_b": nrm(L, hd, W),
                    "A_log": jnp.log(jax.random.uniform(
                        next(keys), (L, nq), jnp.float32, 1.0, 16.0)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "Wbeta": nrm(L, H, nq),
                    "Wg_a": nrm(L, H, hd), "Wg_b": nrm(L, hd, W),
                    "o_norm": jnp.ones((L, hd)),
                    "Wo": nrm(L, W, H)}
            if kind == "mamba2":
                # Mamba-2 (Nemotron-H's init): [z | xBC | dt] side by side;
                # the convolution's taps and bias with the variance of
                # PyTorch's Conv1d default at a fan-in of `taps`; A = 1 ..
                # heads; dt_bias the inverse softplus of dt = logU(1e-3,
                # 1e-1) floored at 1e-4; D = 1
                n, taps = c.ssm_heads, c.conv_kernel
                I = n * c.ssm_head_dim
                xbc = I + 2 * c.ssm_groups * c.ssm_state
                lo, hi = np.log(1e-3), np.log(1e-1)
                dt = jnp.maximum(jnp.exp(jax.random.uniform(
                    next(keys), (L, n), jnp.float32, lo, hi)), 1e-4)
                return {
                    **norms,
                    "in_proj": nrm(L, H, I + xbc + n),
                    "conv_xbc": nrm(L, taps, xbc, std=(3 * taps) ** -0.5),
                    "conv_bias": nrm(L, xbc, std=(3 * taps) ** -0.5),
                    "A_log": jnp.broadcast_to(
                        jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                        (L, n)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "D": jnp.ones((L, n)),
                    "ssm_norm": jnp.ones((L, I)),
                    "out_proj": nrm(L, I, H, std=c.out_proj_init_std
                                    or c.init_std)}
            return {**norms, "conv_in": nrm(L, H, 3 * H),
                    "conv_kernel": nrm(L, c.conv_kernel, H),
                    "conv_out": nrm(L, H, H)}

        I, Ie = c.intermediate, c.expert_intermediate
        S = c.n_shared_experts * (c.shared_intermediate or Ie)

        def dense(kind, L):
            return {**operator(kind, L), "mlp_gate": nrm(L, H, I),
                    "mlp_up": nrm(L, H, I), "mlp_down": nrm(L, I, H)}

        def experts(kind, L):
            gated = c.expert_act == "swiglu"
            if c.layer_pattern is None:
                p = {**operator(kind, L), "router": nrm(L, H, c.n_experts)}
            elif kind != EXPERTS:       # a mixer alone
                p = operator(kind, L)
                del p["norm2"]
                return p
            else:
                p = {"norm1": jnp.ones((L, H)),
                     "router": nrm(L, H, c.n_experts)}
            if gated:
                p["w_gate"] = nrm(L, c.held, H, Ie)
            p.update(w_up=nrm(L, c.held, H, Ie), w_down=nrm(L, c.held, Ie, H))
            if S:
                if gated:
                    p["shared_gate"] = nrm(L, H, S)
                p.update(shared_up=nrm(L, H, S), shared_down=nrm(L, S, H))
            return p

        dense_kind, period, n, rest = c.layout()
        params = {"tok_emb": nrm(c.vocab_size, H, std=c.embedding_init_std)}
        if c.n_dense_layers:
            params["dense"] = dense(dense_kind, c.n_dense_layers)
        # a period of one layer: its stacked parameters themselves
        params["moe"] = experts(period[0], n) if len(period) == 1 \
            else tuple(experts(kind, n) for kind in period)
        params["final_norm"] = jnp.ones((H,))
        if rest:
            params["rest"] = tuple(
                jax.tree_util.tree_map(lambda a: a[0], experts(kind, 1))
                for kind in rest)
        if not c.tie_embeddings:
            params["head"] = nrm(H, c.vocab_size)
        return params

    # ---- forward ----
    def _rows(self, T: int, L: Optional[int]):
        """`(positions [T], the mask as `fused_attention` takes it)` of T
        rows: causal at `0 .. T-1`, or for the diffusion objective the
        block mask over a clean sequence of `L` tokens (T by default) and,
        where T is 2L, its noisy copy before it, both at `0 .. L-1`."""
        if not self._diffusion:
            return jnp.arange(T), {"causal": True}
        L = L or T
        return jnp.arange(T) % L, {
            "block_diffusion": (L, self.config.block_length)}

    def _qkv(self, x, lp, L=None):
        """Queries and keys [B, heads, T, nope + rope] and values
        [B, heads, T, v] of expanded latent attention for `x` [B, T, H],
        and the mask (`_rows`).  Head-first from the products, the rotary
        pairs' two members from products of their own (module docstring:
        the layouts between a product and a kernel), so the rotary lanes of
        q and k stand de-interleaved, all the pairs' first members and then
        their second: every `q . k` is what it is in the checkpoint's order."""
        c = self.config
        B, T, H = x.shape
        nh, dn, dr, dv = c.n_heads, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
        r = c.kv_lora_rank
        pos, mask = self._rows(T, L)

        def heads(x, w):
            # `->btnd` and a transpose, not `->bntd`: only so does XLA's TPU
            # layout assignment let a 128-wide product write [B, heads, T, d]
            # itself (PERF.md, PR 36)
            return jnp.einsum("bth,hnd->btnd", x, w).transpose(0, 2, 1, 3)

        Wq = lp["Wq"].reshape(H, nh, dn + dr)
        Wkvb = lp["Wkvb"].reshape(r, nh, dn + dv)
        kva = x @ lp["Wkva"]
        latent = rms_norm(kva[..., :r], lp["kv_norm"], c.eps)
        # Wq's rotary columns, even ones then odd ones, picked by a 0/1
        # matrix: exact, and cheaper on the chip than two strided slices of
        # a bfloat16 weight and their scatter in the backward
        apart = np.zeros((dr, dr), np.float32)
        apart[np.r_[0:dr:2, 1:dr:2], np.arange(dr)] = 1
        Wq_rope = jnp.einsum("hnd,de->hne", Wq[..., dn:],
                             jnp.asarray(apart, Wq.dtype),
                             precision=jax.lax.Precision.HIGHEST)
        q_rope = rotary_pairs(heads(x, Wq_rope[..., :dr // 2]),
                              heads(x, Wq_rope[..., dr // 2:]), pos,
                              c.rope_base)
        k_rope = rotary_pairs(kva[..., r::2], kva[..., r + 1::2], pos,
                              c.rope_base)          # one rotary head for all
        q = jnp.concatenate([heads(x, Wq[..., :dn]), *q_rope], -1)
        k = jnp.concatenate(
            [heads(latent, Wkvb[..., :dn]),
             jnp.broadcast_to(jnp.concatenate(k_rope, -1)[:, None],
                              (B, nh, T, dr))], -1)
        return q, k, heads(latent, Wkvb[..., dn:]), mask

    def _attention(self, x, lp, L=None):
        """`x + MLA(RMSNorm(x))` for `x` [B, T, H], under `_rows`' mask.
        The kernel's [B, heads, T, v] enters `Wo` with no copy: the product
        contracts over heads and `v` both, whatever their order."""
        c = self.config
        B, T, _ = x.shape
        with jax.named_scope("mla_attention"):
            dt = lp["Wo"].dtype
            q, k, v, mask = self._qkv(
                rms_norm(x, lp["norm1"], c.eps).astype(dt), lp, L)
            o = fused_attention(q, k, v, **mask)
            o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
            return x + (o @ lp["Wo"]).astype(x.dtype)

    def _rotary(self, x, pos):
        """Half-split rotary of `x` [B, T, heads, d] at `pos` [T]; under
        `rope_sections` every stream is `pos` (text: the streams of a
        multimodal rotary coincide)."""
        c = self.config
        if c.rope_sections is None:
            return rotary_half_split(x, pos, c.rope_base)
        return rotary_sections(x, jnp.stack([pos] * len(c.rope_sections)),
                               tuple(c.rope_sections), c.rope_base)

    def _gqa_qkv(self, h, lp, L=None):
        """Queries [B, heads, T, d], keys and values [B, kv heads, T, d] of
        grouped-query attention for the normed `h` [B, T, H], and the mask
        (`_rows`): every query and key head RMS-normed over its
        `head_dim` (one gain each, shared by the heads), then half-split
        rotary on all of it."""
        c = self.config
        B, T, _ = h.shape
        (nh, nkv), hd = c.heads_held, c.head_dim
        qkv = (h @ lp["Wqkv"]).reshape(B, T, nh + 2 * nkv, hd)
        pos, mask = self._rows(T, L)

        def prepared(x, gain):      # the layer's own: either may be off
            x = rms_norm(x, lp[gain], c.eps) if c.qk_norm else x
            return self._rotary(x, pos) if c.rope else x

        q = prepared(qkv[:, :, :nh], "q_norm")
        k = prepared(qkv[:, :, nh:nh + nkv], "k_norm")
        heads_first = (0, 2, 1, 3)
        return (q.transpose(heads_first), k.transpose(heads_first),
                qkv[:, :, nh + nkv:].transpose(heads_first), mask)

    def _gqa_attention(self, x, lp, L=None):
        """`x + GQA(RMSNorm(x))` for `x` [B, T, H], under `_rows`' mask and
        at its positions: the held query heads over their key-value heads
        (`_gqa_qkv`); with `attn_output_gate` the heads' output times
        `sigmoid(h W_g)` elementwise before `W_o` (Gated Attention,
        arXiv:2505.06708).  Of a head share, the held heads' part of `W_o`'s
        product: the other chips' parts are not added."""
        B, T, _ = x.shape
        with jax.named_scope("gqa_attention"):
            dt = lp["Wo"].dtype
            h = rms_norm(x, lp["norm1"], self.config.eps).astype(dt)
            q, k, v, mask = self._gqa_qkv(h, lp, L)
            o = fused_attention(q, k, v, **mask)
            o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
            if self.config.attn_output_gate:
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                    (h @ lp["Wg"]).astype(jnp.float32))).astype(dt)
            return x + (o @ lp["Wo"]).astype(x.dtype)

    def _linear_attention(self, x, lp, L=None):
        """`x + KDA(RMSNorm(x))` for `x` [B, T, H] (Kimi delta attention,
        `ops/linear_attention.py`), and `delta_rule_updates`: the (token,
        held head) pairs whose state update ran with a step above zero.
        For `h` the normed input, of each held head: `q, k, v =
        SiLU(conv(h W_{q,k,v}))` (causal, depthwise), q and k L2-normed;
        the log decay a channel `g = -exp(A_log) softplus(h W_fa W_fb +
        dt_bias)`; `beta = 2 sigmoid(h w_beta)` (negative eigenvalues, as
        Solar Open 2 allows them); the delta rule; then `RMSNorm(o) *
        sigmoid(h W_ga W_gb)` a head and the held heads' part of `W_o`'s
        product.  Convolution, gates, norms and the rule in float32."""
        c = self.config
        B, T, _ = x.shape
        n, d = c.heads_held[0], c.head_dim
        f32 = jnp.float32
        with jax.named_scope("linear_attention"):
            dt = lp["Wo"].dtype
            h = rms_norm(x, lp["norm1"], c.eps).astype(dt)
            qkv = jax.nn.silu(causal_depthwise_conv(
                (h @ lp["Wqkv"]).astype(f32), lp["conv_qkv"].astype(f32)))
            q, k, v = (a.reshape(B, T, n, d).transpose(0, 2, 1, 3)
                       for a in jnp.split(qkv, 3, axis=-1))
            decay = ((h @ lp["Wf_a"]) @ lp["Wf_b"]).astype(f32) \
                + lp["dt_bias"]
            g = (-jnp.exp(lp["A_log"])[:, None]
                 * jax.nn.softplus(decay.reshape(B, T, n, d)))
            beta = 2.0 * jax.nn.sigmoid((h @ lp["Wbeta"]).astype(f32))
            with jax.named_scope("delta_rule"):
                o, _ = chunk_delta_rule(
                    l2_normalize(q), l2_normalize(k), v,
                    g.transpose(0, 2, 1, 3), beta.transpose(0, 2, 1))
            gate = ((h @ lp["Wg_a"]) @ lp["Wg_b"]).astype(f32)
            o = (rms_norm(o.transpose(0, 2, 1, 3), lp["o_norm"], c.eps)
                 * jax.nn.sigmoid(gate.reshape(B, T, n, d)))
            y = o.reshape(B, T, n * d).astype(dt) @ lp["Wo"]
            return x + y.astype(x.dtype), {
                "delta_rule_updates": jnp.sum(beta > 0, dtype=f32)}

    def _mamba2(self, x, lp, L=None):
        """`x + Mamba2(RMSNorm(x))` for `x` [B, T, H] (module docstring):
        the three products, the biased causal convolution and SiLU, `dt`
        and `A`, the SSD (`ops/state_space.ssd`, whose ops carry the scope
        `ssd_scan`), the gated norm over groups and `W_out`.  Convolution,
        `dt`, decays, state and norm in float32."""
        c = self.config
        B, T, _ = x.shape
        n, P, G, N = c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state
        I, f32 = n * P, jnp.float32
        with jax.named_scope("mamba_mixer"):
            dt = lp["out_proj"].dtype
            h = rms_norm(x, lp["norm1"], c.eps).astype(dt)
            w = lp["in_proj"]
            # three products, not one sliced: no pass over a [T, 10304]
            z = h @ w[:, :I]
            xbc = jax.nn.silu(causal_depthwise_conv(
                (h @ w[:, I:-n]).astype(f32), lp["conv_xbc"].astype(f32),
                lp["conv_bias"].astype(f32)))
            step = jax.nn.softplus((h @ w[:, -n:]).astype(f32)
                                   + lp["dt_bias"])
            xs, Bs, Cs = jnp.split(xbc.astype(dt), [I, I + G * N], axis=-1)
            y = ssd(xs.reshape(B, T, n, P), step, -jnp.exp(lp["A_log"]),
                    Bs.reshape(B, T, G, N), Cs.reshape(B, T, G, N), lp["D"],
                    c.ssm_chunk)
            y = gated_rms_norm(y.reshape(B, T, I), z, lp["ssm_norm"], G,
                               c.eps)
            return x + (y.astype(dt) @ lp["out_proj"]).astype(x.dtype)

    def _index(self, h, lp, pos):
        """The indexer's queries [B, n, T, d], its one key head [B, T, d]
        (LayerNorm, then rotary; queries and key half-split rotary on all
        `d` dims) and the heads' weights [B, T, n] in float32 with the
        scale `n^-1/2 d^-1/2` folded in, for `h` [B, T, H]."""
        c = self.config
        B, T, _ = h.shape
        n, d = c.index_heads, c.index_head_dim
        q = rotary_half_split((h @ lp["Wq_idx"]).reshape(B, T, n, d), pos,
                              c.rope_base).transpose(0, 2, 1, 3)
        k = layer_norm_reference(
            (h @ lp["Wk_idx"]).astype(jnp.float32),
            *(lp[name].astype(jnp.float32)
              for name in ("k_idx_gain", "k_idx_bias")), c.eps)
        k = rotary_half_split(k[:, :, None], pos, c.rope_base)[:, :, 0]
        w = (h @ lp["Ww_idx"]).astype(jnp.float32) * (n * d) ** -0.5
        return q, k.astype(h.dtype), w

    def _sparse_attention(self, x, lp, L=None):
        """`x + GQA(RMSNorm(x))` in which every query attends the
        `index_topk` keys its indexer scores highest (`ops/sparse_index.py`),
        and what the layer counted: `selected_keys`, the selected pairs,
        `tie_split_chunks`, the chunks of queries that had ties at a
        threshold to split, and `index_kl`, the indexer's loss — the KL of
        its softmax over a query's selected keys from the main heads' mean
        probabilities there.  The indexer reads the normed input under
        `stop_gradient` and the loss reads the main heads as constants: its
        parameters learn from that loss alone, and nothing else learns from
        it."""
        c = self.config
        B, T, _ = x.shape
        with jax.named_scope("gqa_attention"):
            dt = lp["Wo"].dtype
            h = rms_norm(x, lp["norm1"], c.eps).astype(dt)
            q, k, v, _ = self._gqa_qkv(h, lp, L)    # causal: next_token only
        with jax.named_scope("sparse_index"):
            q_idx, k_idx, w = self._index(jax.lax.stop_gradient(h), lp,
                                          jnp.arange(T))
            selection, selected, split = sparse_index(
                *jax.lax.stop_gradient((q_idx, k_idx, w)), c.index_topk)
        with jax.named_scope("gqa_attention"):
            o, lse = fused_attention(q, k, v, causal=True,
                                     selection=selection, return_lse=True)
            o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
            out = x + (o @ lp["Wo"]).astype(x.dtype)
        with jax.named_scope("index_loss"):
            kl = index_loss(q_idx, k_idx, w, selection.by_query,
                            *jax.lax.stop_gradient((q, k, lse)),
                            c.head_dim ** -0.5)
        return out, {"selected_keys": selected, "index_kl": kl,
                     "tie_split_chunks": split}

    def _short_conv(self, x, lp, L=None):
        """`x + (C * conv(B * X)) W_out` on `RMSNorm(x)`, `x` [B, T, H]."""
        with jax.named_scope("short_conv"):
            dt = lp["conv_out"].dtype
            y = gated_short_conv(
                rms_norm(x, lp["norm1"], self.config.eps).astype(dt),
                lp["conv_in"], lp["conv_kernel"], lp["conv_out"])
            return x + y.astype(x.dtype)

    def _operator(self, kind: str, L=None):
        """`(x, lp) -> x + Op(RMSNorm(x))` of a layer kind (and, of
        `sparse_attention`, what it counted); `L`: `_rows`."""
        return functools.partial(
            {"latent_attention": self._attention,
             "full_attention": self._gqa_attention,
             "sparse_attention": self._sparse_attention,
             "linear_attention": self._linear_attention,
             "mamba2": self._mamba2,
             "conv": self._short_conv}[kind], L=L)

    def _no_counts(self) -> Dict[str, Any]:
        """The counters an expert layer of this model hands back, zero: a
        layer of another kind hands these back."""
        zero = {}
        if self._sparse:
            zero.update(selected_keys=jnp.float32(0), index_kl=jnp.float32(0),
                        tie_split_chunks=jnp.int32(0))
        if self._linear:
            zero["delta_rule_updates"] = jnp.float32(0)
        return zero

    def _trunk(self, params, router_bias, ids, L=None):
        """Hidden states [B, T, H] after the last block (float32: the blocks
        compute in `compute_dtype`, the residual stream they add to does
        not), and what the expert layers counted in this step:
        `expert_load` [L_moe, E] tokens that chose each expert,
        `rows_over_bound` [L_moe] whether the layer's held pairs were more
        than its row bound (`ops/moe.routed_experts`), under the softmax
        router `balance_loss` [L_moe], and in a model with `sparse_attention`
        layers `selected_keys`, `index_kl` and `tie_split_chunks` [L_moe]
        (zero for a layer of another kind); L_moe counts the expert layers,
        under `layer_pattern` the `E` layers alone.  `L`: the clean
        sequence's length where `ids` hold more than it (`_rows`)."""
        c = self.config
        dt = jnp.dtype(c.compute_dtype)

        def cast(lp):
            with jax.named_scope("param_cast"):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), lp)

        def dense_ffn(x, lp):
            with jax.named_scope("dense_mlp"):
                y = swiglu(rms_norm(x, lp["norm2"], c.eps).astype(dt),
                           lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
                return x + y.astype(x.dtype)

        def moe_ffn(x, lp, bias, norm="norm2"):
            B, T, H = x.shape
            y, counts, over, *balance = expert_layer(
                rms_norm(x, lp[norm], c.eps).astype(dt).reshape(B * T, H),
                lp, bias, top_k=c.top_k, scale=c.routed_scale,
                first_held=c.first_expert, eps=c.router_eps,
                score=c.router_score)
            seen = {"expert_load": counts, "rows_over_bound": over}
            if balance:
                seen["balance_loss"] = balance[0]
            return x + y.reshape(B, T, H).astype(x.dtype), seen

        # each block keeps its input, the flash kernel's two results and, of
        # a sparse layer, the selection and the indexer's gradients for the
        # backward pass; the rest is computed again there (module docstring)
        keep = jax.checkpoint_policies.save_only_these_names(
            FLASH_OUT, FLASH_LSE, SELECTION, INDEX_GRADS)
        dense_kind, period, n, rest = c.layout()

        @functools.partial(jax.checkpoint, policy=keep)
        def dense_block(x, lp):
            return dense_ffn(self._operator(dense_kind, L)(x, lp), lp)

        def expert_block(kind, under_scan):
            def moe_block(x, layer):
                lp, bias = layer
                # the router's and the recurrences' parameters stay float32
                lp = {**cast(lp), **{name: lp[name] for name in
                                     ("router", "A_log", "dt_bias", "D")
                                     if name in lp}}
                if c.layer_pattern is not None:     # one sub-block
                    if kind == EXPERTS:
                        return moe_ffn(x, lp, bias, "norm1")
                    return self._operator(kind, L)(x, lp), {}
                x = self._operator(kind, L)(x, lp)
                counted = {}
                if kind in ("sparse_attention", "linear_attention"):
                    x, counted = x
                # every layer of a scan counts alike
                counted = {**self._no_counts(), **counted}
                x, seen = moe_ffn(x, lp, bias)
                return x, {**seen, **counted}
            return jax.checkpoint(moe_block, policy=keep,
                                  prevent_cse=not under_scan)

        with jax.named_scope("embed"):
            x = params["tok_emb"][ids]      # the residual stream is float32
        for i in range(c.n_dense_layers):
            x = dense_block(x, cast(jax.tree_util.tree_map(
                lambda a: a[i], params["dense"])))
        # a scan of ONE trip is no loop to XLA: it inlines the body, and
        # without `prevent_cse` merges the backward's recomputation with the
        # forward, so that every activation of the period stays alive (a
        # `layer_pattern` step compiled to 16.9 GB for a v5e, 12.6 with it).
        # The interleaved configs keep the step they were measured with
        scanned = {kind: expert_block(
            kind, n > 1 or c.layer_pattern is None) for kind in period}
        if len(period) == 1:                # all alike: no remainder either
            return jax.lax.scan(scanned[period[0]], x,
                                (params["moe"], router_bias))

        def over_layers(join, seen):
            return jax.tree_util.tree_map(lambda *a: join(a), *seen)

        # the router bias and the counters: a row an expert layer
        def whole_period(x, layers):
            lps, bias = layers
            seen = []
            for j, kind in enumerate(period):
                x, s_j = scanned[kind](
                    x, (lps[j], bias[len(seen)] if c.routes(kind) else None))
                if c.routes(kind):
                    seen.append(s_j)
            return x, over_layers(jnp.stack, seen)

        in_periods = n * sum(map(c.routes, period))
        x, seen = jax.lax.scan(
            whole_period, x,
            (params["moe"],
             router_bias[:in_periods].reshape(n, -1, c.n_experts)))
        seen = [jax.tree_util.tree_map(
            lambda a: a.reshape(in_periods, *a.shape[2:]), seen)]
        unrolled = {kind: expert_block(kind, False) for kind in rest}
        row = in_periods
        for j, kind in enumerate(rest):
            x, s_j = unrolled[kind](
                x, (params["rest"][j],
                    router_bias[row] if c.routes(kind) else None))
            if c.routes(kind):
                seen.append(jax.tree_util.tree_map(lambda a: a[None], s_j))
                row += 1
        return x, over_layers(jnp.concatenate, seen)

    def _logits(self, params, hidden):
        """float32 logits [..., vocab] over the vocabulary held."""
        dt = jnp.dtype(self.config.compute_dtype)
        h = rms_norm(hidden, params["final_norm"], self.config.eps)
        with jax.named_scope("lm_head"):
            if self.config.tie_embeddings:  # the embedding's rows, untransposed
                return jnp.einsum("...h,vh->...v", h.astype(dt),
                                  params["tok_emb"].astype(dt),
                                  preferred_element_type=jnp.float32)
            return jnp.dot(h.astype(dt), params["head"].astype(dt),
                           preferred_element_type=jnp.float32)

    def _loss(self, params, router_bias, ids, labels):
        """Mean next-token cross-entropy over every position but the last
        of each sequence (`labels[:, t]` is the id at `t + 1`; the last
        column is ignored), logits and `log_softmax` in float32."""
        hidden, seen = self._trunk(params, router_bias, ids)
        logits = self._logits(params, hidden)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
            return self._indexed(*self._balanced(jnp.mean(nll[:, :-1]),
                                                 seen))

    def _indexed(self, loss, seen):
        """`loss` plus `index_loss_coef` times the layers' mean indexer
        loss, where layers have one; `seen["index_kl"]` becomes that mean
        (the step's counter)."""
        if "index_kl" not in seen:
            return loss, seen
        kl = jnp.mean(seen["index_kl"])
        return loss + self.config.index_loss_coef * kl, {**seen,
                                                         "index_kl": kl}

    def _balanced(self, loss, seen):
        """`loss` plus `aux_loss_coef` times the expert layers' mean
        `balance_loss`, where the router is one that has it; `seen` without
        it (what is left are the step's counters)."""
        if "balance_loss" not in seen:
            return loss, seen
        seen = dict(seen)
        return (loss + self.config.aux_loss_coef
                * jnp.mean(seen.pop("balance_loss")), seen)

    # ---- the block-diffusion objective ----
    def _noise(self, key, ids):
        """`(noisy ids, weight)` for clean `ids` [B, L], both [B, L]: each
        block of `block_length` tokens draws `t ~ U(noise_eps, 1)` and each
        of its tokens becomes `mask_token_id` with probability `t`; `weight`
        is `1/t` at a replaced position and 0 elsewhere (float32)."""
        c = self.config
        rows, L = ids.shape
        if L % c.block_length:
            raise ValueError(f"{L} tokens are no whole blocks of "
                             f"{c.block_length}")
        with jax.named_scope("bd_noise"):
            k_t, k_mask = jax.random.split(key)
            t = jnp.repeat(
                jax.random.uniform(k_t, (rows, L // c.block_length),
                                   jnp.float32, c.noise_eps, 1.0),
                c.block_length, axis=1)
            replaced = jax.random.uniform(k_mask, (rows, L)) < t
            return (jnp.where(replaced, jnp.int32(c.mask_token_id), ids),
                    jnp.where(replaced, 1.0 / t, 0.0))

    def _noisy_half(self, params, router_bias, ids, noisy_ids):
        """float32 logits [B, L, vocab] of the noisy copy's rows from the
        forward over `[noisy ; clean]` (the clean half needs no head), and
        what the expert layers counted."""
        L = ids.shape[1]
        with jax.named_scope("bd_noise"):
            both = jnp.concatenate([noisy_ids, ids], axis=1)
        hidden, seen = self._trunk(params, router_bias, both, L)
        return self._logits(params, hidden[:, :L]), seen

    def _masked_token_loss(self, params, router_bias, ids, noisy_ids,
                           weight):
        """`mean over sequences of (1/L) sum_i weight_i * -log
        softmax(logits_i)[ids_i]`: a replaced position predicts its own
        token, weighted by `1/t` of its block.  Beside `_trunk`'s counters,
        `masked_positions`: how many positions carried loss."""
        logits, seen = self._noisy_half(params, router_bias, ids, noisy_ids)
        with jax.named_scope("diffusion_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
            loss = jnp.mean(jnp.sum(weight * nll, axis=1) / ids.shape[1])
            return loss, {**seen, "masked_positions": jnp.sum(
                weight > 0, dtype=jnp.int32)}

    def _diffusion_loss(self, params, router_bias, ids, noisy_ids, weight):
        """The step's loss: `_masked_token_loss` and the balance term."""
        loss, seen = self._masked_token_loss(params, router_bias, ids,
                                             noisy_ids, weight)
        with jax.named_scope("diffusion_loss"):
            return self._balanced(loss, seen)

    def noise_key(self, iteration):
        """The key of the step at `iteration`: what `fit` draws there."""
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), iteration)

    # ---- compiled steps ----
    def _step_body(self):
        speed = self.config.bias_update_speed

        def step(params, opt_state, state, iteration, epoch, ids, labels):
            if self._diffusion:     # `labels` repeat the ids: not read
                loss_of, targets = self._diffusion_loss, self._noise(
                    self.noise_key(iteration), ids)
            else:
                loss_of, targets = self._loss, (labels,)
            (loss, seen), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, state["router_bias"], ids,
                                       *targets)
            with jax.named_scope("updater"):
                upd, new_opt = self.updater.apply(opt_state, grads, iteration,
                                                  epoch, params=params)
                new_params = jax.tree_util.tree_map(lambda p, u: p - u,
                                                    params, upd)
            with jax.named_scope("router_bias"):
                new_state = {
                    # the selection bias is the sigmoid router's
                    "router_bias": update_router_bias(
                        state["router_bias"], seen["expert_load"], speed)
                    if self.config.router_score == "sigmoid"
                    else state["router_bias"],
                    **{name: state[name] + seen[name] for name in seen}}
            return new_params, new_opt, new_state, loss, iteration + 1

        return step

    def _step(self):
        if "step" not in self._steps:
            self._steps["step"] = jax.jit(self._step_body(),
                                          donate_argnums=(0, 1, 2))
            self._unnoted_step = True     # `fit_batch` tells `monitor`
        return self._steps["step"]

    def _scan_step(self):
        if "scan" not in self._steps:
            from deeplearning4j_tpu.utils.scan_fit import make_scan_step
            body = self._step_body()

            def tick(carry, epoch, batch):
                p, o, s, it = carry
                p, o, s, loss, it = body(p, o, s, it, epoch, *batch)
                return (p, o, s, it), loss

            self._steps["scan"] = make_scan_step(tick)
        return self._steps["scan"]

    # ---- public API ----
    def fit(self, iterator, epochs: int = 1) -> "DecoderModel":
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            with span("fit_epoch", model=type(self).__name__):
                for mds in iterator:
                    self.fit_batch(mds)
            self.epoch += 1
        return self

    def _batch(self, mds):
        (ids,) = [jnp.asarray(f) for f in mds.features]
        (labels,) = [jnp.asarray(l) for l in mds.labels]
        self._tokens = ids.shape[-2] * ids.shape[-1]
        # every row goes through the experts: under diffusion both copies
        self._pairs = (self._tokens * self.config.top_k
                       * (2 if self._diffusion else 1))
        return ids.astype(jnp.int32), labels.astype(jnp.int32)

    def fit_batch(self, mds):
        from deeplearning4j_tpu.utils.counters import advance, device_counters
        ids, labels = self._batch(mds)
        it, ep = device_counters(self)
        t0 = time.perf_counter()
        step = self._step()
        args = (self.params_, self.opt_state_, self.state_, it, ep, ids,
                labels)
        (self.params_, self.opt_state_, self.state_, loss,
         new_it) = step(*args)
        note("step_dispatch", t0, time.perf_counter(), self.iteration)
        if self._unnoted_step:      # the call compiled it: its text is
            self._unnoted_step = False      # `monitor.lowered_step()`
            note_step(step, args)
        self._score = loss
        advance(self, new_it)
        return loss          # a device scalar: `score()` reads it lazily

    def fit_steps(self, mds):
        """k train steps in one dispatch: `ids` and `labels` carry a leading
        `[k, batch]` steps axis.  Same math as k `fit_batch` calls; returns
        the length-k loss array."""
        from deeplearning4j_tpu.utils.counters import advance, device_counters
        from deeplearning4j_tpu.utils.scan_fit import check_steps_axes
        ids, labels = self._batch(mds)
        k = check_steps_axes([("ids", ids), ("labels", labels)])
        it, ep = device_counters(self)
        t0 = time.perf_counter()
        ((self.params_, self.opt_state_, self.state_, new_it), losses,
         last_loss) = self._scan_step()(
            (self.params_, self.opt_state_, self.state_, it), ep,
            (ids, labels))
        note("step_dispatch", t0, time.perf_counter(), self.iteration)
        self._score = last_loss
        advance(self, new_it, steps=int(k))
        return losses

    def score(self) -> float:
        s = getattr(self, "_score", None)
        return float(s) if s is not None else float("nan")

    def output(self, ids, noisy_ids=None):
        """float32 logits [B, T, vocab held] of the inference forward
        (under the diffusion objective: causal over blocks, bidirectional
        inside one).  With `noisy_ids` [B, T], the clean `ids` with some
        replaced by the mask token: the training forward over both copies,
        the logits of the noisy copy's rows."""
        bias = self.state_["router_bias"]
        if noisy_ids is not None:
            if not self._diffusion:
                raise ValueError("noisy_ids go with the block_diffusion "
                                 "objective")
            if "noisy_half" not in self._steps:
                self._steps["noisy_half"] = jax.jit(
                    lambda p, b, i, n: self._noisy_half(p, b, i, n)[0])
            return self._steps["noisy_half"](
                self.params_, bias, jnp.asarray(ids, jnp.int32),
                jnp.asarray(noisy_ids, jnp.int32))
        if "output" not in self._steps:
            self._steps["output"] = jax.jit(
                lambda p, b, i: self._logits(p, self._trunk(p, b, i)[0]))
        return self._steps["output"](self.params_, bias,
                                     jnp.asarray(ids, jnp.int32))

    def noise(self, ids, key):
        """`(noisy ids, weight)` as a step whose key is `key` draws them for
        `ids` [B, L] (`_noise`; `noise_key(iteration)` is a step's key)."""
        if "noise" not in self._steps:
            self._steps["noise"] = jax.jit(self._noise)
        return self._steps["noise"](key, jnp.asarray(ids, jnp.int32))

    def diffusion_loss(self, ids, key):
        """The masked-token loss of `ids` [B, L] under the noise of `key`,
        without the balance term: a device scalar.  A fixed key compares
        like with like across steps."""
        if "diffusion_loss" not in self._steps:
            self._steps["diffusion_loss"] = jax.jit(
                lambda p, b, i, key: self._masked_token_loss(
                    p, b, i, *self._noise(key, i))[0])
        return self._steps["diffusion_loss"](
            self.params_, self.state_["router_bias"],
            jnp.asarray(ids, jnp.int32), key)

    def expert_load(self) -> np.ndarray:
        """[expert layers, n_experts] tokens that chose each expert over all
        train steps so far: one device read of the step's own counter."""
        return np.asarray(self.state_["expert_load"])

    def routed_rows(self) -> Dict[str, Any]:
        """What the routed experts' row bound did over all train steps so
        far (one device read): `steps`; `steps_over_bound` [expert layers],
        the steps in which the layer's held pairs were more than `bound`
        rows and it took more than one pass over them; `bound` and `pairs`
        at the newest batch shape (0 before the first step)."""
        c = self.config
        return {"steps": self.iteration,
                "steps_over_bound": np.asarray(
                    self.state_["rows_over_bound"]),
                "bound": row_bound(self._pairs, c.held, c.n_experts),
                "pairs": self._pairs}

    def noise_stats(self) -> Dict[str, Any]:
        """What the diffusion objective's noise did over all train steps so
        far (one device read): `steps`; `masked_positions`, the positions
        that were replaced and carried loss; `share` of the clean tokens
        that is, at the newest batch shape (about a half: the mean of t)."""
        masked = int(self.state_["masked_positions"])
        seen = self.iteration * self._tokens
        return {"steps": self.iteration, "masked_positions": masked,
                "share": masked / seen if seen else 0.0}

    def sparse_stats(self) -> Dict[str, Any]:
        """What the `sparse_attention` layers' indexers did over all train
        steps so far (one device read): `steps`; `selected_keys`, the pairs
        they kept; `keys_per_query`, that over the steps' queries at the
        newest batch shape and the sparse layers (`sum_t min(t + 1,
        index_topk) / T` where nothing else binds: 1,920.06 at 16,384
        tokens and 2,048 keys); `index_kl`, the steps' mean indexer loss;
        `tie_split_chunks`, the chunks of queries (of `T / 1,024` a layer a
        sequence) in which some query's threshold was shared by more keys
        than it could keep, so that the chunk searched for each row's last
        kept tie."""
        selected, kl, split = jax.device_get(
            [self.state_[name] for name in
             ("selected_keys", "index_kl", "tie_split_chunks")])
        selected = float(selected.sum())
        queries = (self.iteration * self._tokens
                   * self.config.kinds.count("sparse_attention"))
        return {"steps": self.iteration, "selected_keys": selected,
                "keys_per_query": selected / queries if queries else 0.0,
                "index_kl": float(kl) / self.iteration
                if self.iteration else 0.0,
                "tie_split_chunks": int(split.sum())}

    def linear_stats(self) -> Dict[str, Any]:
        """What the `linear_attention` layers did over all train steps so
        far (one device read): `steps`; `delta_rule_updates`, the (token,
        held head) pairs whose state update ran with a step above zero;
        `per_token`, that over the steps' tokens at the newest batch shape:
        the held heads times the linear layers where nothing is skipped."""
        updates = float(np.asarray(self.state_["delta_rule_updates"]).sum())
        tokens = self.iteration * self._tokens
        return {"steps": self.iteration, "delta_rule_updates": updates,
                "per_token": updates / tokens if tokens else 0.0}

    def selection(self, ids):
        """bool [B, T, T]: the keys each query of `ids` [B, T] keeps in the
        FIRST layer (a `sparse_attention` layer), whose input is the
        embedding alone."""
        if self.config.kinds[0] != "sparse_attention":
            raise ValueError("the first layer selects no keys")
        if "selection" not in self._steps:
            dt = jnp.dtype(self.config.compute_dtype)

            def first(params, ids):
                stacked = params["moe"]
                lp = jax.tree_util.tree_map(
                    lambda a: a[0].astype(dt),
                    stacked if isinstance(stacked, dict) else stacked[0])
                x = params["tok_emb"][ids]
                h = rms_norm(x, lp["norm1"], self.config.eps).astype(dt)
                return unpack_selection(sparse_index(
                    *self._index(h, lp, jnp.arange(ids.shape[1])),
                    self.config.index_topk)[0])

            self._steps["selection"] = jax.jit(first)
        return self._steps["selection"](self.params_,
                                        jnp.asarray(ids, jnp.int32))

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self.params_))

    # ---- persistence ----
    _TREES = ("params_", "opt_state_", "state_")

    def save(self, path):
        """`path` is a file name or a seekable binary file object."""
        import io, json, zipfile
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("config.json", json.dumps(
                {**dataclasses.asdict(self.config), "seed": self.seed,
                 "iteration": self.iteration, "epoch": self.epoch}))
            for name in self._TREES:
                buf = io.BytesIO()
                np.savez(buf, *[np.asarray(l) for l in
                                jax.tree_util.tree_leaves(
                                    getattr(self, name))])
                z.writestr(name + ".npz", buf.getvalue())

    @staticmethod
    def load(path, updater: Optional[IUpdater] = None) -> "DecoderModel":
        import io, json, zipfile
        with zipfile.ZipFile(path) as z:
            meta = json.loads(z.read("config.json").decode())
            iteration, epoch = meta.pop("iteration"), meta.pop("epoch")
            seed = meta.pop("seed", 0)      # (files from before it was kept)
            model = DecoderModel(DecoderConfig(**meta), seed=seed,
                                 updater=updater)
            for name in model._TREES:
                leaves, treedef = jax.tree_util.tree_flatten(
                    getattr(model, name))
                with np.load(io.BytesIO(z.read(name + ".npz"))) as d:
                    # (a counter newer than the file starts at zero:
                    # `tie_split_chunks`, the state's last leaf, PR 38)
                    setattr(model, name, jax.tree_util.tree_unflatten(
                        treedef, [
                            leaf if name == "state_" and f"arr_{i}" not in d
                            else jnp.asarray(d[f"arr_{i}"])
                            for i, leaf in enumerate(leaves)]))
            model.iteration, model.epoch = iteration, epoch
        return model
