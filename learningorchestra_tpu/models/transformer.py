"""Sequence transformer — the long-context model family (dp × tp × sp).

One block function with options. With every option off it is the seed's
small classifier (LayerNorm, learned positions, fused multi-head
attention through the ring, GELU MLP, mean-pool head). The options are
what today's open language models are built from, each switched by its
own ``TxConfig`` field, so a published architecture is a set of numbers
and not a second model file:

- ``rms_norm``: RMSNorm without bias instead of LayerNorm;
- ``n_kv_heads``: grouped-query attention with separate q/k/v
  projections of ``head_dim``, ``rope_theta`` rotary positions over the
  whole head (rotate-half), ``qk_norm`` an RMSNorm per head on q and k;
- ``indexer_heads``: learned sparse attention (DeepSeek-Sparse-Attention
  as Keye-VL-2.0 configures it): a small indexer scores every earlier
  position, a query attends to the ``indexer_topk`` best, and the
  indexer learns from an alignment loss against the main attention's
  own probabilities. A block of queries attends through the Pallas
  kernels of ``ops/pallas_kernels.py:chosen_attention`` wherever the
  TPU's compiler can tile the shapes (heads 128 wide, whole mask
  tiles): key blocks stream through VMEM, which holds the online
  softmax's running maximum, sum and accumulator and one score tile,
  and stop at the causal edge, so no score block exists in HBM; other
  shapes run the plain einsum body, the kernels' oracle;
- ``n_experts``: a routed expert layer that is TOLD WHICH EXPERTS IT
  HOLDS (``experts_first``, ``experts_held``): it routes over all of
  them and adds only its own experts' part, which is what one chip of
  an expert-parallel deployment computes. No token is dropped. On the
  TPU only the routed pairs are computed: the assignments sorted by
  expert, through the grouped products of
  ``ops/pallas_kernels.py:grouped_matmul`` (megablox's schedule);
- ``lm_head``: a per-position next-token loss over an untied vocabulary
  head, with the label-token readout that keeps the family a classifier
  (class ``c`` is token id ``c``; a row's target at its last position is
  its label token);
- ``layer_pattern``: a model is one PERIOD of layer kinds, repeated
  (``"LLLF"``: three linear layers, then a full-attention one). ``L`` is
  the gated delta-rule linear attention of Gated DeltaNet (Yang et al.,
  arXiv:2412.06464) as Olmo-Hybrid configures it (``linear_heads``,
  ``linear_key_dim``, ``linear_value_dim``, ``linear_conv``,
  ``linear_neg_eigval``): a depthwise causal conv, L2-normed queries and
  keys, and per head a (key dim, value dim) state that decays and takes
  a delta-rule write a token. The program computes it a chunk of
  ``linear_chunk`` tokens at a time (within a chunk the ``(I +
  tril(diag(beta) K K^T * decay))^-1`` transform, across chunks a loop
  that carries the state); the benchmark's reference runs the
  recurrence token by token. The transform's inverse is BUILT, in
  float32 and by exact algebra, not handed to XLA's serial solver:
  for a chunk of whole groups of 8 rows up to 64 by
  ``ops/pallas_kernels.py:delta_transform`` (blocked forward
  substitution, every system of a block in the lanes of one kernel
  call), for any other length by block recursion in plain
  ``jax.numpy`` (``_block_inverse``: diagonal blocks of 16 by forward
  substitution, merged 16 -> 32 -> 64 by batched products, the
  kernel's oracle); then one product with the right-hand side, and in
  the backward two (``_chunk_transform``);
- ``gated_width``: a gated MLP without bias, ``(silu(h Wg) * (h Wu))
  Wd``; ``post_norm``: the norm sits on a sublayer's OUTPUT, ``x +
  norm(f(x))`` (OLMo 2's order); ``qk_norm_whole``: one RMSNorm over the
  whole q / k projection; ``no_positions``: neither a learned table nor
  rotary (position reaches such a model through its linear layers);
- ``heads_held``: attention of both kinds is TOLD HOW MANY OF ITS HEADS
  IT HOLDS: it computes their part of ``o Wo`` and adds nothing for the
  absent ones, which is what one chip of a tensor-parallel deployment
  computes before the reduce. (Which heads they are changes no
  computation, so no option names them: a holder's leaves are its own
  heads'.)
- ``M`` in ``layer_pattern`` is Mamba-2's mixer (Dao and Gu,
  arXiv:2405.21060) as Nemotron-H configures it (``ssm_heads``,
  ``ssm_head_dim``, ``ssm_state``, ``ssm_groups``, ``ssm_conv``): a
  depthwise causal conv with a bias, and per head a (head dim, state)
  float32 state that decays by ``exp(dt A)`` and takes ``dt x B^T`` a
  token, read out through ``C``; gate, then a norm over groups. The
  program runs the scan a chunk of ``ssm_chunk`` tokens at a time (the
  state-space duality's matrix form within a chunk, the carried state
  across chunks); the benchmark's reference runs it token by token;
- ``E`` in ``layer_pattern``: the expert sublayer is a layer of its own,
  and then EVERY layer is one sublayer (``M``, ``E``, or ``F`` attention
  alone). The expert layer's options: ``router_sigmoid`` (sigmoid scores
  in float32, the top-k chosen by score plus a correction bias that no
  gradient trains), ``routed_scale`` (the gates' scaling factor),
  ``relu2_experts`` (``relu(h U)^2 D``, not gated), ``shared_width``
  (with relu^2 experts, one shared expert of that form on every token,
  riding the routed experts' token-block loop).

The reference has no sequence models (SURVEY.md §5); the plain
reference these options are held to is the benchmark's
(``perfbench/reference_tx.py``, the published equations in float32).

The training step is one SPMD program over the 3-axis mesh
(parallel/mesh.py):

- ``data``  — batch rows sharded;
- ``model`` — Megatron-style tensor parallelism: attention heads, the
  FFN hidden dimension and the held experts are split, output
  projections reduce with one ``psum`` per block;
- ``seq``   — context parallelism: exact attention as a ring of
  ``ppermute`` hops (parallel/ring_attention.py). The ring rotates every
  K/V block past every query, so a query that attends to chosen keys
  cannot ride it: with the indexer on, a ``seq`` axis > 1 raises.

Differentiation goes *through* ``shard_map``; the layers are stacked and
scanned (with a ``layer_pattern`` the scan runs over periods, each
kind's leaves stacked on their own), so the program compiles one layer,
or one period, whatever the depth. Every
array is float32 and every large product runs at the backend's default
precision: on a TPU that is bfloat16 operands with float32 accumulation
and float32 gradients, with no second copy of the weights in a lower
type; only the router's small product asks for float32 operands.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.ops import pallas_kernels as pk
from learningorchestra_tpu.parallel.mesh import (
    DATA_AXIS, MODEL_AXIS, SEQ_AXIS)
from learningorchestra_tpu.parallel.ring_attention import (
    reference_attention, ring_attention)


@dataclass(frozen=True)
class TxConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 256
    n_classes: int = 2
    max_len: int = 1024
    causal: bool = False          # classifier default; True for LM-style
    #: Rematerialize each layer's activations in the backward pass
    #: (jax.checkpoint): O(1)-in-depth live activation memory for one
    #: more forward pass a step (its cost on the chip: not measured
    #: alone; the 8k-token cell cannot run without it).
    remat: bool = False
    # --- the architecture options (all off: the block above) -------------
    rms_norm: bool = False
    norm_eps: float = 1e-5
    n_kv_heads: int = 0           # > 0: grouped-query attention
    head_dim: int = 0             # 0: d_model // n_heads
    rope_theta: float = 0.0       # > 0: rotary positions, no learned table
    qk_norm: bool = False
    indexer_heads: int = 0        # > 0: learned sparse attention
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    q_chunk: int = 512            # queries scored at a time (tiling only)
    n_experts: int = 0            # > 0: routed experts instead of the MLP
    experts_per_token: int = 8
    expert_width: int = 768
    experts_first: int = 0        # the experts this holder was told it has
    experts_held: int = 0         # 0: all of them
    norm_topk_prob: bool = True
    lm_head: bool = False
    token_chunk: int = 1024       # positions an expert / head pass holds
    init_std: float = 0.02        # the options' init (normal, this std)
    layer_pattern: str = ""       # one period of kinds: F full, L linear
    linear_heads: int = 0         # the linear mixer's key = value heads
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    linear_conv: int = 4          # depthwise causal conv width
    linear_neg_eigval: bool = False   # beta in (0, 2) instead of (0, 1)
    linear_chunk: int = 64        # tokens a chunk transform holds (tiling)
    gated_width: int = 0          # > 0: gated MLP without bias, this wide
    post_norm: bool = False       # x + norm(f(x)) instead of x + f(norm(x))
    qk_norm_whole: bool = False   # one RMSNorm over the whole q / k
    no_positions: bool = False    # neither learned nor rotary positions
    heads_held: int = 0           # heads of each mixer held here; 0: all
    ssm_heads: int = 0            # the Mamba-2 mixer's heads (M layers)
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1           # B / C groups; a group's heads share them
    ssm_conv: int = 4             # depthwise causal conv width
    ssm_chunk: int = 128          # tokens a chunk of the scan holds (tiling)
    router_sigmoid: bool = False  # sigmoid scores + correction bias
    routed_scale: float = 1.0     # the routed gates' scaling factor
    relu2_experts: bool = False   # relu(h U)^2 D instead of gated SiLU
    shared_width: int = 0         # > 0: one shared expert, this wide

    def __post_init__(self):
        if (self.rope_theta or self.qk_norm or self.indexer_heads
                or self.qk_norm_whole) and not self.n_kv_heads:
            raise ValueError("rope_theta, qk_norm, qk_norm_whole and "
                             "indexer_heads are options of grouped-query "
                             "attention: set n_kv_heads")
        if self.qk_norm and self.qk_norm_whole:
            raise ValueError("qk_norm (per head) and qk_norm_whole are "
                             "two kinds of one norm: set one")
        if self.no_positions and self.rope_theta:
            raise ValueError("no_positions and rope_theta exclude each "
                             "other")
        if set(self.layer_pattern) - set("FLME"):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: a "
                             "period is made of F (full attention), L "
                             "(linear attention), M (Mamba-2) and E "
                             "(experts)")
        period = len(self.layer_pattern)
        if period and self.n_layers > period and self.n_layers % period:
            raise ValueError(f"n_layers {self.n_layers} is neither a "
                             f"multiple of the period {self.layer_pattern!r}"
                             " nor a cut of it")
        if "L" in self.pattern:
            if not self.linear_heads:
                raise ValueError("an L layer needs linear_heads")
            if not self.causal:
                raise ValueError("the linear layer's state runs forward "
                                 "over the row: it needs causal attention")
            if self.linear_chunk < 1 or self.linear_conv < 1:
                raise ValueError("linear_chunk and linear_conv are at "
                                 "least 1")
        if "M" in self.pattern:
            if not self.ssm_heads or self.ssm_heads % self.ssm_groups:
                raise ValueError("an M layer needs ssm_heads, a multiple "
                                 f"of ssm_groups {self.ssm_groups}")
            if not self.causal:
                raise ValueError("the Mamba-2 state runs forward over the "
                                 "row: it needs causal attention")
            if self.ssm_chunk < 1 or self.ssm_conv < 1:
                raise ValueError("ssm_chunk and ssm_conv are at least 1")
        if "E" in self.pattern and not self.n_experts:
            raise ValueError("an E layer needs n_experts")
        if (self.shared_width or self.router_sigmoid or self.relu2_experts
                or self.routed_scale != 1.0) and not self.n_experts:
            raise ValueError("shared_width, router_sigmoid, relu2_experts "
                             "and routed_scale are options of the expert "
                             "layer: set n_experts")
        if self.shared_width and not self.relu2_experts:
            raise ValueError("the shared expert has the relu^2 form: set "
                             "relu2_experts")
        if not 0 <= self.heads_held <= self.n_heads:
            raise ValueError(f"heads_held {self.heads_held} is more than "
                             f"the {self.n_heads} heads")
        for key in ("n_kv_heads", "linear_heads"):
            if getattr(self, key) * self.heads % self.n_heads:
                raise ValueError(
                    f"{self.heads} of {self.n_heads} heads held is no "
                    f"whole share of {key} {getattr(self, key)}")
        if self.n_kv_heads and self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {self.n_heads} is not a multiple of "
                             f"n_kv_heads {self.n_kv_heads}")
        if self.indexer_heads and not self.causal:
            raise ValueError("the indexer scores earlier positions: it "
                             "needs causal attention")
        if self.n_experts:
            held = self.experts_held or self.n_experts
            if not 0 <= self.experts_first <= self.n_experts - held:
                raise ValueError(
                    f"experts [{self.experts_first}, "
                    f"{self.experts_first + held}) are not among the "
                    f"{self.n_experts} routed experts")
            if not 1 <= self.experts_per_token <= self.n_experts:
                raise ValueError("experts_per_token must lie in "
                                 f"[1, {self.n_experts}]")
        if self.lm_head and not self.causal:
            raise ValueError("a next-token loss needs causal attention")
        if self.lm_head and self.n_classes > self.vocab:
            raise ValueError(f"{self.n_classes} label tokens do not fit a "
                             f"vocabulary of {self.vocab}")

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def pattern(self) -> str:
        """The period as it is run: cut to ``n_layers`` where the model
        is shallower; empty where every layer is the same layer."""
        return self.layer_pattern[:self.n_layers]

    @property
    def heads(self) -> int:
        """Query heads held here; ``kv_heads`` and ``lin_heads`` are the
        same share of the key-value and of the linear mixer's heads."""
        return self.heads_held or self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads * self.heads // self.n_heads

    @property
    def lin_heads(self) -> int:
        return self.linear_heads * self.heads // self.n_heads

    @property
    def one_sublayer(self) -> bool:
        """Every layer is one sublayer: a period that names E holds the
        expert sublayer as a layer of its own, and its F, L and M layers
        have no MLP."""
        return "E" in self.pattern

    @property
    def has_state(self) -> bool:
        """A layer carries a recurrent state (L or M)."""
        return bool(set(self.pattern) & set("LM"))

    @property
    def n_full(self) -> int:
        """Full-attention layers in the model."""
        if not self.pattern:
            return self.n_layers
        return self.pattern.count("F") * (self.n_layers // len(self.pattern))


class Axes(NamedTuple):
    """The mesh axis names a forward pass reduces over; ``None``: that
    axis does not exist (the unsharded forward of predict and tests)."""
    data: Optional[str] = None
    model: Optional[str] = None
    seq: Optional[str] = None


MESH_AXES = Axes(DATA_AXIS, MODEL_AXIS, SEQ_AXIS)
NO_AXES = Axes()

#: Gradient-norm groups of a step's report: leaf name → group. The
#: benchmark's comparison reads them by these names. (The GELU MLP's
#: leaves have counted under "experts" since PR 34; the gated MLP's
#: are "mlp".)
GRAD_GROUPS = {
    "embed": "embedding", "pos": "embedding",
    "head_w": "head", "head_b": "head", "lnf_g": "head",
    "ln1_g": "attention", "ln1_b": "attention", "wqkv": "attention",
    "wq": "attention", "wk": "attention", "wv": "attention",
    "wo": "attention", "q_norm": "attention", "k_norm": "attention",
    "ix_wq": "indexer", "ix_wk": "indexer", "ix_kn_g": "indexer",
    "ix_kn_b": "indexer", "ix_ww": "indexer",
    "router": "router", "router_bias": "router",
    "ln2_g": "experts", "ln2_b": "experts", "we_gate": "experts",
    "we_up": "experts", "we_down": "experts",
    "sh_up": "shared_expert", "sh_down": "shared_expert",
    "w1": "experts", "b1": "experts", "w2": "experts", "b2": "experts",
    "w_gate": "mlp", "w_up": "mlp", "w_down": "mlp",
    **{"la_" + k: "linear_attention" for k in (
        "ln_g", "ln_b", "wq", "wk", "wv", "wz", "wb", "wa", "cq", "ck",
        "cv", "a_log", "dt_bias", "gn_g", "wo")},
    **{"ssm_" + k: "ssm" for k in (
        "ln_g", "ln_b", "wz", "wx", "wbc", "wdt", "cx", "cbc", "cx_b",
        "cbc_b", "a_log", "dt_bias", "d", "gn_g", "wo")},
}

def _psum(x, axis):
    return x if axis is None else jax.lax.psum(x, axis)


def _axis_size(axis) -> int:
    return 1 if axis is None else jax.lax.psum(1, axis)


def _axis_index(axis):
    return 0 if axis is None else jax.lax.axis_index(axis)


# --- parameters -------------------------------------------------------------

def _layer_leaves(cfg: TxConfig) -> Dict[str, Dict[str, Any]]:
    """One layer's leaves by the kind of layer that has them, ``{kind:
    {name: (shape, init, model dim)}}``: ``F`` the full-attention
    sublayer's, ``L`` the linear mixer's, ``M`` the Mamba-2 mixer's,
    ``*`` every layer's (the MLP or expert sublayer), or ``E``'s where
    the expert sublayer is a layer of its own. The mixers' heads, and
    Mamba-2's B / C groups, lie on the model axis. ``init`` is "normal",
    "ones", "zeros", "a_log"
    or "dt_bias"; ``model dim`` the dim split over the model axis
    (heads, FFN hidden, held experts), ``None``: replicated."""
    d, H, hd = cfg.d_model, cfg.heads, cfg.hd
    bias = not cfg.rms_norm
    full = {"ln1_g": ((d,), "ones", None)}
    if bias:
        full["ln1_b"] = ((d,), "zeros", None)
    if cfg.n_kv_heads:
        G = cfg.kv_heads
        full.update(wq=((d, H, hd), "normal", 1), wk=((d, G, hd), "normal", 1),
                    wv=((d, G, hd), "normal", 1), wo=((H, hd, d), "normal", 0))
        if cfg.qk_norm:
            full.update(q_norm=((hd,), "ones", None),
                        k_norm=((hd,), "ones", None))
        if cfg.qk_norm_whole:
            full.update(q_norm=((H, hd), "ones", 0),
                        k_norm=((G, hd), "ones", 0))
    else:
        full.update(wqkv=((d, 3, H, hd), "normal", 2),
                    wo=((H, hd, d), "normal", 0))
    if cfg.indexer_heads:
        Hi, di = cfg.indexer_heads, cfg.indexer_head_dim
        full.update(ix_wq=((d, Hi, di), "normal", None),
                    ix_wk=((d, di), "normal", None),
                    ix_kn_g=((di,), "ones", None),
                    ix_kn_b=((di,), "zeros", None),
                    ix_ww=((d, Hi), "normal", None))
    Hl, dk, dv, K = (cfg.lin_heads, cfg.linear_key_dim, cfg.linear_value_dim,
                     cfg.linear_conv)
    linear = {"la_ln_g": ((d,), "ones", None),
              "la_wq": ((d, Hl, dk), "normal", 1),
              "la_wk": ((d, Hl, dk), "normal", 1),
              "la_wv": ((d, Hl, dv), "normal", 1),
              "la_wz": ((d, Hl, dv), "normal", 1),
              "la_wb": ((d, Hl), "normal", 1), "la_wa": ((d, Hl), "normal", 1),
              "la_cq": ((K, Hl, dk), "normal", 1),
              "la_ck": ((K, Hl, dk), "normal", 1),
              "la_cv": ((K, Hl, dv), "normal", 1),
              "la_a_log": ((Hl,), "a_log", 0),
              "la_dt_bias": ((Hl,), "dt_bias", 0),
              "la_gn_g": ((dv,), "ones", None),
              "la_wo": ((Hl, dv, d), "normal", 0)}
    if bias:
        linear["la_ln_b"] = ((d,), "zeros", None)
    Hs, P, N, Gs = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, \
        cfg.ssm_groups
    ssm = {"ssm_ln_g": ((d,), "ones", None),
           "ssm_wz": ((d, Hs, P), "normal", 1),
           "ssm_wx": ((d, Hs, P), "normal", 1),
           "ssm_wbc": ((d, 2, Gs, N), "normal", 2),
           "ssm_wdt": ((d, Hs), "normal", 1),
           "ssm_cx": ((cfg.ssm_conv, Hs, P), "normal", 1),
           "ssm_cbc": ((cfg.ssm_conv, 2, Gs, N), "normal", 2),
           "ssm_cx_b": ((Hs, P), "zeros", 0),
           "ssm_cbc_b": ((2, Gs, N), "zeros", 1),
           "ssm_a_log": ((Hs,), "a_log", 0),
           "ssm_dt_bias": ((Hs,), "dt_bias", 0),
           "ssm_d": ((Hs,), "ones", 0),
           "ssm_gn_g": ((Hs, P), "ones", 0),
           "ssm_wo": ((Hs, P, d), "normal", 0)}
    if bias:
        ssm["ssm_ln_b"] = ((d,), "zeros", None)
    every = {"ln2_g": ((d,), "ones", None)}
    if bias:
        every["ln2_b"] = ((d,), "zeros", None)
    if cfg.n_experts:
        E, f = cfg.held, cfg.expert_width
        every["router"] = ((d, cfg.n_experts), "normal", None)
        if cfg.router_sigmoid:
            every["router_bias"] = ((cfg.n_experts,), "zeros", None)
        if not cfg.relu2_experts:
            every["we_gate"] = ((E, d, f), "normal", 0)
        every.update(we_up=((E, d, f), "normal", 0),
                     we_down=((E, f, d), "normal", 0))
        if cfg.shared_width:
            every.update(sh_up=((d, cfg.shared_width), "normal", 1),
                         sh_down=((cfg.shared_width, d), "normal", 0))
    elif cfg.gated_width:
        f = cfg.gated_width
        every.update(w_gate=((d, f), "normal", 1), w_up=((d, f), "normal", 1),
                     w_down=((f, d), "normal", 0))
    else:
        every.update(w1=((d, cfg.d_ff), "normal", 1),
                     b1=((cfg.d_ff,), "zeros", 0),
                     w2=((cfg.d_ff, d), "normal", 0), b2=((d,), "zeros", None))
    pattern = cfg.pattern or "F"
    return {kind: leaves for kind, leaves in (
        ("F", full), ("L", linear), ("M", ssm),
        ("E" if cfg.one_sublayer else "*", every))
        if kind == "*" or kind in pattern}


def _stacking(cfg: TxConfig, kind: str) -> tuple:
    """The axes a layer leaf of ``kind`` is stacked on, leading: the
    layers where every layer is the same layer; with a pattern the
    periods, then that kind's layers within a period."""
    if not cfg.pattern:
        return (cfg.n_layers,)
    count = len(cfg.pattern) if kind == "*" else cfg.pattern.count(kind)
    return (cfg.n_layers // len(cfg.pattern), count)


def _leaf_shapes(cfg: TxConfig) -> Dict[str, Any]:
    """``{name: (shape, init)}`` top-level and ``{"layers": {...}}`` with
    the stacking axes leading (``_stacking``)."""
    d = cfg.d_model
    top = {"embed": ((cfg.vocab, d), "normal")}
    if not (cfg.rope_theta or cfg.no_positions):
        top["pos"] = ((cfg.max_len, d), "normal")
    if cfg.lm_head:
        top["lnf_g"] = ((d,), "ones")
        top["head_w"] = ((d, cfg.vocab), "normal")
    else:
        top["head_w"] = ((d, cfg.n_classes), "normal")
        top["head_b"] = ((cfg.n_classes,), "zeros")
    lay = {name: (_stacking(cfg, kind) + shape, init)
           for kind, leaves in _layer_leaves(cfg).items()
           for name, (shape, init, _) in leaves.items()}
    return dict(top, layers=lay)


def has_options(cfg: TxConfig) -> bool:
    """Any architecture option on. The leaves then draw ``normal *
    init_std``, leaf ``i`` in sorted-name order from ``fold_in(key, i)``
    (a recipe a second implementation can follow without this file:
    perfbench's does), and predict takes a block of rows at a time."""
    return bool(cfg.rms_norm or cfg.n_kv_heads or cfg.n_experts
                or cfg.lm_head or cfg.layer_pattern or cfg.gated_width)


def leaf_order(cfg: TxConfig) -> list:
    """Leaf paths in the order the init recipe numbers them."""
    shapes = _leaf_shapes(cfg)
    top = sorted(k for k in shapes if k != "layers")
    return top + [f"layers.{k}" for k in sorted(shapes["layers"])]


def init_params(key, cfg: TxConfig) -> Dict[str, Any]:
    shapes = _leaf_shapes(cfg)
    arch = has_options(cfg)

    def make(i, name, shape, init):
        if init in ("ones", "zeros"):
            return (jnp.ones if init == "ones" else jnp.zeros)(
                shape, jnp.float32)
        k = jax.random.fold_in(key, i)
        if init == "a_log":     # a head's decay rate, exp(a_log) in [1, 16)
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        if init == "dt_bias":   # inverse softplus of a step in [1e-3, 1e-1)
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if arch:
            scale = cfg.init_std
        elif name in ("embed", "pos"):
            scale = 0.02
        elif name == "wo":
            scale = 1.0 / np.sqrt(cfg.d_model)
        else:   # fan-in of the seed's block: the first non-layer dim
            scale = 1.0 / np.sqrt(shape[1] if name in (
                "wqkv", "w1", "w2") else shape[0])
        return jax.random.normal(k, shape, jnp.float32) * scale

    params: Dict[str, Any] = {"layers": {}}
    for i, path in enumerate(leaf_order(cfg)):
        if path.startswith("layers."):
            name = path[len("layers."):]
            params["layers"][name] = make(i, name, *shapes["layers"][name])
        else:
            params[path] = make(i, path, *shapes[path])
    return params


def param_specs(cfg: TxConfig) -> Dict[str, Any]:
    """PartitionSpec per leaf: heads, FFN hidden and held experts on the
    model axis, the rest replicated (stacking axes first, never split)."""
    specs = {k: P() for k in _leaf_shapes(cfg) if k != "layers"}
    specs["layers"] = {}
    for kind, leaves in _layer_leaves(cfg).items():
        lead = (None,) * len(_stacking(cfg, kind))
        for name, (shape, _, dim) in leaves.items():
            specs["layers"][name] = P() if dim is None else P(*lead, *(
                MODEL_AXIS if i == dim else None for i in range(len(shape))))
    return specs


# --- the block's parts ------------------------------------------------------

def _rms(x, g, eps: float):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rms_whole(x, g, eps: float, ax: Axes):
    """One RMSNorm over the whole projection x (B, T, heads, D), weight
    ``g`` (heads, D): the mean of squares is over every channel HELD
    (on a model axis, over the axis; what absent heads would add to it
    is left out, as the deployment's one chip cannot know it)."""
    ss = _psum((x * x).sum((-2, -1), keepdims=True), ax.model)
    n = x.shape[-2] * x.shape[-1] * _axis_size(ax.model)
    return x * jax.lax.rsqrt(ss / n + eps) * g


def _norm(cfg: TxConfig, x, g, b=None):
    if cfg.rms_norm:
        return _rms(x, g, cfg.norm_eps)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + cfg.norm_eps) * g + b


def _rope(x, pos, theta: float):
    """Rotate-half rotary embedding over the whole last dim of
    x (B, T, heads, D) or (B, T, D), positions ``pos`` (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # (T, half)
    if x.ndim == 4:
        ang = ang[:, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _select_topk(scores, k: int):
    """``scores`` (C, T) float32, ``-inf`` where not allowed. The mask of
    each row's ``k`` largest (all of a row with fewer; ties at the k-th
    value all kept): the k-th largest is found exactly, by bisection
    over the floats' ordered bit patterns, 32 counting passes."""
    x = scores + 0.0                                  # -0.0 -> +0.0
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    ukey = jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)

    def body(b, ans):
        cand = ans | (jnp.uint32(1) << (jnp.uint32(31) - b.astype(jnp.uint32)))
        enough = (ukey >= cand[:, None]).sum(-1) >= k
        return jnp.where(enough, cand, ans)

    ans = jax.lax.fori_loop(0, 32, body, ukey[:, 0] * jnp.uint32(0))
    return ukey >= ans[:, None]


def _chunk(n: int, cap: int) -> int:
    """Items a blocked pass holds at a time: the largest divisor of
    ``n`` that is at most ``cap``."""
    return next(c for c in range(min(cap, n), 0, -1) if n % c == 0)


def _query_blocks(cfg: TxConfig, ax: Axes, T: int):
    """How grouped-query attention runs for rows of ``T`` tokens a
    shard, decided once from the configuration, the axes and the static
    shapes: ``None`` where it does not go through ``_chosen_attention``
    (dense attention, and causal attention on a sequence axis: the
    ring); else ``(C, key_block)``: the queries a block holds, and the
    key block ``pk.chosen_attention``'s kernels walk, 0 where the block
    runs the plain body."""
    if not cfg.n_kv_heads or not (
            cfg.indexer_heads or (ax.seq is None and cfg.causal)):
        return None
    C = _chunk(T, cfg.q_chunk)
    return C, pk.chosen_attn_key_block(T, C, cfg.hd,
                                       cfg.n_heads // cfg.n_kv_heads)


def attention_path(cfg: TxConfig, ax: Axes, T: int) -> Dict[str, Any]:
    """``_query_blocks``' choice as span attributes: ``attn_kernel``
    (share of attention layers on the Pallas kernels; the layers are
    alike, so 1.0 or 0.0) and ``key_blocks_skipped_share`` (the (query
    block, key block) pairs that start past the query block's last
    query, which the kernels neither fetch nor compute; the plain body
    scores them all). Empty where attention does not go through
    ``_chosen_attention``."""
    blocks = _query_blocks(cfg, ax, T)
    if blocks is None:
        return {}
    C, key_block = blocks
    skipped = 0.0
    if key_block:
        run = sum(((i + 1) * C - 1) // key_block + 1 for i in range(T // C))
        skipped = 1.0 - run / ((T // C) * (T // key_block))
    return {"attn_kernel": float(key_block > 0),
            "key_blocks_skipped_share": skipped}


def _chosen_attention(cfg: TxConfig, ax: Axes, blocks, q, k, v, ix):
    """Causal grouped-query attention of ONE row over the keys the
    indexer chose (every earlier key where there is no indexer), a chunk
    of queries at a time; the chunk body is rematerialised.

    ``blocks`` is ``_query_blocks``' ``(C, key_block)``. Where the TPU's
    compiler can tile the shapes and a group's working set fits VMEM
    (``pk.chosen_attn_key_block``: heads 128 wide, a chunk of whole mask
    tiles) the scores, the softmax and the probability-times-value
    product of a chunk are ``pk.chosen_attention``'s kernels: key blocks
    stream through VMEM, which holds the running maximum, sum and
    accumulator of the chunk's heads, and stop at the causal edge; no
    score block exists in HBM. Any other shape runs the plain body
    below, whose (heads, chunk, T) float32 score block lives once; it
    is the kernels' oracle.

    q (T, H, D); k, v (T, G, D); ``ix``: ``None`` or the indexer's
    ``(qI (T, Hi, Di), kI (T, Di), w (T, Hi))``. Returns ``(o (T, H, D),
    stats)``: ``stats`` = [index loss summed over queries, keys kept
    summed, queries with fewer keys than top-k]."""
    T, H, D = q.shape
    G = k.shape[1]
    R = H // G
    C, fused = blocks[0], blocks[1] > 0
    if fused:      # heads side by side in lanes, once a row (a copy)
        k, v = k.reshape(T, G * D), v.reshape(T, G * D)
    kpos = jnp.arange(T)
    heads_all = H * _axis_size(ax.model)

    def chunk(i):
        q_c = jax.lax.dynamic_slice_in_dim(q, i * C, C, 0)
        allowed = kpos[None, :] <= (i * C + jnp.arange(C))[:, None]
        if ix is not None:
            qi_c = jax.lax.dynamic_slice_in_dim(ix[0], i * C, C, 0)
            w_c = jax.lax.dynamic_slice_in_dim(ix[2], i * C, C, 0)
            sc = jnp.einsum("qjd,kd->qjk", qi_c, ix[1])
            score = (jax.nn.relu(sc) * w_c[:, :, None]).sum(1) * (
                cfg.indexer_head_dim ** -0.5 * cfg.indexer_heads ** -0.5)
            chosen = allowed & _select_topk(
                jnp.where(allowed, jax.lax.stop_gradient(score), -jnp.inf),
                cfg.indexer_topk)
        else:
            chosen = allowed
        if fused:
            o, heads_p = pk.chosen_attention(q_c, k, v, chosen, i)
        else:
            s = jnp.einsum("qgrd,kgd->grqk", q_c.reshape(C, G, R, D),
                           k) * D ** -0.5
            p = jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1)
            o = jnp.einsum("grqk,kgd->qgrd", p, v).reshape(C, H, D)
            heads_p = p.sum((0, 1))
        kept = chosen.sum(-1)
        stats = jnp.stack([
            kept.sum().astype(jnp.float32),
            (kept < cfg.indexer_topk).sum().astype(jnp.float32)])
        if ix is None:
            return o, jnp.concatenate([jnp.zeros(1), stats])
        # The alignment loss: KL(main attention's head-summed
        # probabilities over the chosen keys, L1-normalised, detached ||
        # the indexer's softmax over the same keys).
        target = _psum(jax.lax.stop_gradient(heads_p), ax.model) / heads_all
        logq = jax.nn.log_softmax(jnp.where(chosen, score, -jnp.inf), -1)
        kl = jnp.where(target > 0, target * (
            jnp.log(jnp.where(target > 0, target, 1.0)) - jnp.where(
                chosen, logq, 0.0)), 0.0).sum()
        return o, jnp.concatenate([kl[None], stats])

    # The kernels' output and log-sum-exp of every chunk are kept (a
    # row's (T, H, D) and (T, H) a layer): the backward then recomputes
    # the selection and the head-summed probabilities, not the forward.
    keep = jax.checkpoint_policies.save_only_these_names(*pk.ATTN_RESIDUALS)
    o, stats = jax.lax.map(jax.checkpoint(chunk, policy=keep),
                           jnp.arange(T // C))
    return o.reshape(T, H, D), stats.sum(0)


def _attention(cfg: TxConfig, ax: Axes, h, lyr, pos):
    """The block's attention half on the normed input ``h`` (B, T, d):
    ``(out (B, T, d) before the model-axis reduce, stats (3,))``."""
    if not cfg.n_kv_heads:
        qkv = jnp.einsum("btd,dkhe->btkhe", h, lyr["wqkv"])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn = (ring_attention(q, k, v, axis_name=ax.seq, causal=cfg.causal)
                if ax.seq is not None else
                reference_attention(q, k, v, causal=cfg.causal))
        return jnp.einsum("bthe,hed->btd", attn, lyr["wo"]), jnp.zeros(3)
    q = jnp.einsum("btd,dhe->bthe", h, lyr["wq"])
    k = jnp.einsum("btd,dge->btge", h, lyr["wk"])
    v = jnp.einsum("btd,dge->btge", h, lyr["wv"])
    if cfg.qk_norm:
        q = _rms(q, lyr["q_norm"], cfg.norm_eps)
        k = _rms(k, lyr["k_norm"], cfg.norm_eps)
    if cfg.qk_norm_whole:
        q = _rms_whole(q, lyr["q_norm"], cfg.norm_eps, ax)
        k = _rms_whole(k, lyr["k_norm"], cfg.norm_eps, ax)
    if cfg.rope_theta:
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    if ax.seq is not None and _axis_size(ax.seq) == 1:
        ax = ax._replace(seq=None)       # a row is whole here: no ring
    blocks = _query_blocks(cfg, ax, h.shape[1])
    if blocks is not None:
        ix = None
        if cfg.indexer_heads:
            # The indexer reads the block's input detached: only its own
            # alignment loss trains it, and that loss trains nothing else.
            hi = jax.lax.stop_gradient(h)
            qi = jnp.einsum("btd,dje->btje", hi, lyr["ix_wq"])
            ki = jnp.einsum("btd,de->bte", hi, lyr["ix_wk"])
            mu = ki.mean(-1, keepdims=True)
            ki = (ki - mu) * jax.lax.rsqrt(
                ((ki - mu) ** 2).mean(-1, keepdims=True) + cfg.norm_eps
            ) * lyr["ix_kn_g"] + lyr["ix_kn_b"]
            if cfg.rope_theta:
                qi, ki = (_rope(qi, pos, cfg.rope_theta),
                          _rope(ki, pos, cfg.rope_theta))
            ix = (qi, ki, jnp.einsum("btd,dj->btj", hi, lyr["ix_ww"]))

        def row(args):
            return _chosen_attention(cfg, ax, blocks, *args[:3],
                                     None if ix is None else args[3:])

        o, stats = jax.lax.map(row, (q, k, v) + (ix or ()))
        stats = stats.sum(0)
    else:
        # Dense GQA: each key/value head repeated for its group of query
        # heads; over a sharded sequence, the ring.
        rep = cfg.n_heads // cfg.n_kv_heads
        k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
        o = (reference_attention(q, k, v, causal=False) if ax.seq is None
             else ring_attention(q, k, v, axis_name=ax.seq,
                                 causal=cfg.causal))
        stats = jnp.zeros(3)
    return jnp.einsum("bthe,hed->btd", o, lyr["wo"]), stats


#: Tokens a pass of the linear mixer's loop holds (tiling only; whole
#: chunks): 256 read 71.2 ms a layer forward + backward on the v5e
#: against 75.3 at 512 and 81 at 1,024 (PERF.md section 5, PR 36).
_LINEAR_BLOCK = 256

#: Precision of the delta rule's products (the in-chunk transform and
#: the products with the carried state); the state, the transform and
#: every sum in them are float32 whatever this says.
_DELTA_PRECISION = jax.lax.Precision.HIGHEST


#: Side of the diagonal blocks the chunk transform inverts by forward
#: substitution; larger blocks are merged from these on the MXU.
_TRANSFORM_BASE = 16


def _diagonal_blocks(A, s: int):
    """The (s, s) diagonal blocks of A (..., C, C): (..., C // s, s, s)."""
    m = A.shape[-1] // s
    A = A.reshape(A.shape[:-2] + (m, s, m, s))
    return jnp.stack([A[..., r, :, r, :] for r in range(m)], axis=-3)


def _block_inverse(A):
    """``(I + A)^-1`` of strictly lower-triangular ``A`` (..., C, C) by
    block recursion, exact algebra in float32 (entries of ``A`` on or
    above the diagonal are not read). The diagonal blocks of
    ``min(C, _TRANSFORM_BASE)`` are inverted by forward substitution,
    row ``i`` of a block's inverse being ``e_i - sum_{j<i} A[i, j] *
    row_j``, all blocks of all systems at once; then pairs of
    neighbouring blocks are merged, 16 -> 32 -> 64 -> .., with ``inv([[P,
    0], [R, Q]]) = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]`` as batched
    products at ``_DELTA_PRECISION``. A ``C`` that is not the base times
    a power of two is padded to the next such size with identity rows,
    which invert to themselves."""
    C = A.shape[-1]
    s = size = min(C, _TRANSFORM_BASE)
    while size < C:
        size *= 2
    if size > C:
        A = jnp.pad(A, [(0, 0)] * (A.ndim - 2) + [(0, size - C)] * 2)
    dot = partial(jnp.matmul, precision=_DELTA_PRECISION)
    D = _diagonal_blocks(A, s)
    eye = jnp.eye(s, dtype=A.dtype)
    rows = []
    for i in range(s):
        rows.append(sum((-D[..., i, j, None] * rows[j] for j in range(i)),
                        jnp.broadcast_to(eye[i], D.shape[:-1])))
    T = jnp.stack(rows, axis=-2)                       # (..., size // s, s, s)
    while s < size:
        R = _diagonal_blocks(A, 2 * s)[..., s:, :s]
        P, Q = T[..., 0::2, :, :], T[..., 1::2, :, :]
        X = -dot(dot(Q, R), P)
        T = jnp.concatenate([
            jnp.concatenate([P, jnp.zeros_like(P)], axis=-1),
            jnp.concatenate([X, Q], axis=-1)], axis=-2)
        s *= 2
    return T[..., 0, :C, :C]


def _inverse(A):
    """``(I + A)^-1`` of the chunks' ``A`` (..., C, C) by what the static
    chunk length admits: ``pk.delta_transform``'s one kernel call, else
    the plain recursion, the kernel's oracle."""
    if pk.delta_transform_fits(A.shape[-1], bool(jax.typeof(A).vma)):
        return pk.delta_transform(A)
    return _block_inverse(A)


@jax.custom_vjp
def _chunk_transform(A):
    """``T = (I + A)^-1`` of the chunks' strictly lower-triangular ``A``
    (``_inverse``). Differentiated as the inverse it is, ``dA = -tril(
    T^T dT T^T, -1)``: two batched products, no pass back through the
    substitution."""
    return _inverse(A)


def _chunk_transform_fwd(A):
    T = _inverse(A)
    return T, T


def _chunk_transform_bwd(T, dT):
    dot = partial(jnp.matmul, precision=_DELTA_PRECISION)
    Tt = jnp.swapaxes(T, -1, -2)
    return (-jnp.tril(dot(dot(Tt, dT), Tt), -1),)


_chunk_transform.defvjp(_chunk_transform_fwd, _chunk_transform_bwd)


def delta_path(cfg: TxConfig, ax: Axes) -> Dict[str, Any]:
    """``_inverse``'s choice as a span attribute: ``delta_transform`` =
    ``"block_inverse_kernel"`` where the linear layers' chunk transform
    runs ``pk.delta_transform``, ``"block_inverse"`` where the plain
    recursion. Empty without a linear layer."""
    if "L" not in cfg.pattern:
        return {}
    kernel = pk.delta_transform_fits(cfg.linear_chunk, any(ax))
    return {"delta_transform":
            "block_inverse_kernel" if kernel else "block_inverse"}


def _delta_block(q, k, v, g, beta, state, C: int):
    """The gated delta rule over one block of whole chunks, chunk by
    chunk. Per head, with ``alpha_t = exp(g_t)``, the recurrence is
    ``S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - (alpha_t S_{t-1})^T
    k_t))^T``, ``o_t = S_t^T q_t``. A chunk of ``C`` tokens with
    ``G_i = g_1 + .. + g_i`` turns it into matrix products: ``A = tril(
    diag(beta) K K^T * exp(G_i - G_j), -1)``; ``[W, U] = (I + A)^-1
    [beta * exp(G) * K, beta * V]`` (the in-chunk transform: ``T = (I +
    A)^-1`` is built by blocks, ``_chunk_transform``, then one product
    with the right-hand side; no call to XLA's solver); then with the
    state ``S`` the chunk starts from, ``V' = U - W S``, ``O = (Q *
    exp(G)) S + tril(Q K^T * exp(G_i - G_j)) V'``, ``S <- exp(G_C) S +
    (K * exp(G_C - G))^T V'``. The transforms of the block's chunks are
    computed together; the walk over its chunks is unrolled.

    q, k (B, S, H, dk) (q scaled, both L2-normed); v (B, S, H, dv); g,
    beta (B, S, H); state (B, H, dk, dv); ``S`` a multiple of ``C``.
    Returns ``(o (B, S, H, dv), state after the block, largest |state|
    a chunk of it ended on)``, all float32."""
    B, S, H, dk = q.shape
    n = S // C
    dot = partial(jnp.einsum, precision=_DELTA_PRECISION)

    def chunks(x):             # (B, S, H, ...) -> (B, H, n, C, ...)
        x = x.reshape((B, n, C, H) + x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                             # (B, H, n, C)
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))                   # i >= j, else 0
    kk = dot("bhnik,bhnjk->bhnij", k, k)
    A = jnp.where(jnp.tril(lower, -1), beta[..., None] * kk * decay, 0.0)
    rhs = jnp.concatenate([(beta * jnp.exp(G))[..., None] * k,
                           beta[..., None] * v], axis=-1)
    wu = dot("bhnij,bhnjd->bhnid", _chunk_transform(A), rhs)
    W, U = wu[..., :dk], wu[..., dk:]
    qk = dot("bhnik,bhnjk->bhnij", q, k) * decay
    q_in = q * jnp.exp(G)[..., None]
    k_out = k * jnp.exp(G[..., -1:] - G)[..., None]
    out, peak = [], state[0, 0, 0, 0] * 0.0
    for c in range(n):
        v_new = U[:, :, c] - dot("bhik,bhkv->bhiv", W[:, :, c], state)
        out.append(dot("bhik,bhkv->bhiv", q_in[:, :, c], state)
                   + dot("bhij,bhjv->bhiv", qk[:, :, c], v_new))
        state = (jnp.exp(G[:, :, c, -1])[..., None, None] * state
                 + dot("bhik,bhiv->bhkv", k_out[:, :, c], v_new))
        peak = jnp.maximum(peak, jnp.abs(jax.lax.stop_gradient(state)).max())
    o = jnp.stack(out, axis=2)                             # (B, H, n, C, dv)
    return jnp.moveaxis(o, 1, 3).reshape(B, S, H, -1), state, peak


def _linear_attention(cfg: TxConfig, ax: Axes, h, lyr):
    """The linear mixer on ``h`` (B, T, d): projections, then ONE loop
    over blocks of ``_LINEAR_BLOCK`` tokens that carries the (B, heads,
    key dim, value dim) float32 state and holds everything from the
    depthwise conv to the gated norm (a trace tells the mixer's core by
    that carried shape), then the output projection. The loop's body is
    rematerialised in the backward pass, which differentiates through
    it. Returns ``(out (B, T, d) before the model-axis reduce, the
    largest |state| reached)``."""
    B, T, _ = h.shape
    dk, dv, K, C = (cfg.linear_key_dim, cfg.linear_value_dim,
                    cfg.linear_conv, cfg.linear_chunk)
    H = lyr["la_wq"].shape[1]                    # this model shard's heads
    u = jnp.concatenate([jnp.einsum("btd,dhe->bthe", h, lyr[w])
                         for w in ("la_wq", "la_wk", "la_wv")], axis=-1)
    z = jnp.einsum("btd,dhe->bthe", h, lyr["la_wz"])
    ba = jnp.stack([jnp.einsum("btd,dh->bth", h, lyr[w])
                    for w in ("la_wb", "la_wa")], axis=-1)
    # Whole chunks: the row padded with zero inputs at its end, which
    # write nothing into a state that no later token reads.
    n = -(-T // C)
    S = C * _chunk(n, max(1, _LINEAR_BLOCK // C))
    pad = [(0, 0), (0, n * C - T)]
    u, z, ba = (jnp.pad(x, pad + [(0, 0)] * (x.ndim - 2)).reshape(
        (B, n * C // S, S) + x.shape[2:]).swapaxes(0, 1) for x in (u, z, ba))
    conv = jnp.concatenate([lyr["la_cq"], lyr["la_ck"], lyr["la_cv"]], -1)
    rate = -jnp.exp(lyr["la_a_log"])

    def l2(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    def block(carry, xs):
        state, tail, peak = carry
        u_b, z_b, ba_b = xs
        seen = jnp.concatenate([tail, u_b], axis=1)       # K - 1 earlier
        c = jax.nn.silu(sum(conv[j] * seen[:, j:j + S] for j in range(K)))
        q = l2(c[..., :dk]) * dk ** -0.5
        k, v = l2(c[..., dk:2 * dk]), c[..., 2 * dk:]
        beta = jax.nn.sigmoid(ba_b[..., 0]) * (
            2.0 if cfg.linear_neg_eigval else 1.0)
        g = rate * jax.nn.softplus(ba_b[..., 1] + lyr["la_dt_bias"])
        o, state, top = _delta_block(q, k, v, g, beta, state, C)
        y = _rms(o, lyr["la_gn_g"], cfg.norm_eps) * jax.nn.silu(z_b)
        return (state, seen[:, S:], jnp.maximum(peak, top)), y

    zero = u[0, 0, 0, 0, 0] * 0.0     # varies over the mesh as the inputs do
    start = (jnp.zeros((B, H, dk, dv), jnp.float32) + zero,
             jnp.zeros((B, K - 1, H, 2 * dk + dv), jnp.float32) + zero,
             zero)
    (_, _, peak), y = jax.lax.scan(jax.checkpoint(block), start, (u, z, ba))
    y = y.swapaxes(0, 1).reshape(B, n * C, H, dv)[:, :T]
    return jnp.einsum("bthe,hed->btd", y, lyr["la_wo"]), peak


#: Tokens a pass of the Mamba-2 mixer's loop holds (tiling only; whole
#: chunks).
_SSM_BLOCK = 256

#: Precision of the scan's products (C B^T, the scores with dt x, C with
#: the carried state, the state's update): the TPU's default for float32,
#: bfloat16 operands and float32 sums, as every large product of the
#: block. The state, the decay and its cumulative sums are float32
#: whatever this says.
_SSM_PRECISION = jax.lax.Precision.DEFAULT


def _ssm_dot(spec: str, a, b):
    """One product of the scan (``_SSM_PRECISION``)."""
    return jnp.einsum(spec, a, b, precision=_SSM_PRECISION)


def ssm_path(cfg: TxConfig) -> Dict[str, Any]:
    """The scan the M layers run, as span attributes: ``ssm_path``
    ``"chunked"`` (the program has no other; the benchmark's reference
    runs the recurrence token by token) and ``ssm_chunk``. Empty without
    an M layer."""
    if "M" not in cfg.pattern:
        return {}
    return {"ssm_path": "chunked", "ssm_chunk": cfg.ssm_chunk}


def _ssd_block(x, dt, a, b, c, state, C: int):
    """Mamba-2's scan over one block of whole chunks. Per head ``h`` of
    group ``g`` the recurrence is ``S_t = exp(a_t) S_{t-1} + dt_t x_t
    b_t^T``, ``y_t = S_t c_t`` (``a_t = dt_t A_h <= 0``). A chunk of ``C``
    tokens with ``A_i = a_1 + .. + a_i`` (float32) is matrix products:
    ``L_ij = exp(A_i - A_j)`` for ``i >= j``, else 0; ``y = (L * (c
    b^T)) (dt x) + exp(A) (c S^T)`` with ``S`` the state the chunk starts
    from; ``S <- exp(A_C) S + (exp(A_C - A) dt x)^T b``. The chunks' own
    parts are computed together; the walk over a block's chunks is
    unrolled.

    x (B, S, H, P); dt, a (B, S, H); b, c (B, S, G, N); state (B, H, P,
    N); ``S`` a multiple of ``C``. Returns ``(y (B, S, H, P), state after
    the block, largest |state| a chunk of it ended on)``."""
    Bsz, S, H, P = x.shape
    G, N = b.shape[2:]
    n, r = S // C, H // G

    def chunks(t):             # (B, S, ...) -> (B, n, C, ...)
        return t.reshape((Bsz, n, C) + t.shape[2:])

    xdt = chunks((x * dt[..., None]).reshape(Bsz, S, G, r, P))
    b, c = chunks(b), chunks(c)
    A = jnp.cumsum(jnp.moveaxis(chunks(a.reshape(Bsz, S, G, r)), 2, -1),
                   axis=-1)                                # (B, n, G, r, C)
    lower = jnp.tril(jnp.ones((C, C), bool))
    L = jnp.exp(jnp.where(lower, A[..., :, None] - A[..., None, :],
                          -jnp.inf))                       # i >= j, else 0
    cb = _ssm_dot("bnigs,bnjgs->bngij", c, b)
    y = _ssm_dot("bngrij,bnjgrp->bnigrp", cb[:, :, :, None] * L, xdt)
    into = jnp.exp(A[..., -1:] - A)                        # to the chunk end
    dS = _ssm_dot("bnjgrp,bnjgs->bngrps",
                  xdt * jnp.moveaxis(into, -1, 2)[..., None], b)
    state = state.reshape(Bsz, G, r, P, N)
    out, peak = [], state[0, 0, 0, 0, 0] * 0.0
    for k in range(n):
        read = _ssm_dot("bigs,bgrps->bigrp", c[:, k], state)
        out.append(y[:, k] + read * jnp.moveaxis(jnp.exp(A[:, k]), -1, 1)[
            ..., None])
        state = jnp.exp(A[:, k, :, :, -1])[..., None, None] * state + dS[:, k]
        peak = jnp.maximum(peak, jnp.abs(jax.lax.stop_gradient(state)).max())
    y = jnp.stack(out, axis=1)                             # (B, n, C, G, r, P)
    return y.reshape(Bsz, S, H, P), state.reshape(Bsz, H, P, N), peak


def _ssm_mixer(cfg: TxConfig, ax: Axes, h, lyr):
    """The Mamba-2 mixer on ``h`` (B, T, d): the input projections
    (``z``, ``x``, ``B`` / ``C``, ``dt``), then ONE loop over blocks of
    ``_SSM_BLOCK`` tokens that carries the (B, heads, head dim, state)
    float32 state and the conv's last ``ssm_conv - 1`` inputs and holds
    everything from the conv to the gated norm (a trace tells the
    mixer's core by that carried shape), then the output projection. The
    loop's body is rematerialised in the backward pass, which
    differentiates through it. Returns ``(out (B, T, d) before the
    model-axis reduce, the largest |state| reached)``."""
    B, T, _ = h.shape
    P, N, K, C = cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_chunk
    H, G = lyr["ssm_wx"].shape[1], lyr["ssm_wbc"].shape[2]   # held here
    u = jnp.concatenate([
        jnp.einsum("btd,dhp->bthp", h, lyr["ssm_wx"]).reshape(B, T, H * P),
        jnp.einsum("btd,dkgn->btkgn", h, lyr["ssm_wbc"]).reshape(
            B, T, 2 * G * N)], axis=-1)
    z = jnp.einsum("btd,dhp->bthp", h, lyr["ssm_wz"])
    dt = jnp.einsum("btd,dh->bth", h, lyr["ssm_wdt"])
    # Whole chunks: the row padded with zero inputs at its end, which no
    # earlier token reads.
    n = -(-T // C)
    S = C * _chunk(n, max(1, _SSM_BLOCK // C))
    pad = [(0, 0), (0, n * C - T)]
    u, z, dt = (jnp.pad(x, pad + [(0, 0)] * (x.ndim - 2)).reshape(
        (B, n * C // S, S) + x.shape[2:]).swapaxes(0, 1) for x in (u, z, dt))
    conv = jnp.concatenate([lyr["ssm_cx"].reshape(K, H * P),
                            lyr["ssm_cbc"].reshape(K, 2 * G * N)], -1)
    conv_b = jnp.concatenate([lyr["ssm_cx_b"].reshape(-1),
                              lyr["ssm_cbc_b"].reshape(-1)])
    rate = -jnp.exp(lyr["ssm_a_log"])

    def block(carry, xs):
        state, tail, peak = carry
        u_b, z_b, dt_b = xs
        seen = jnp.concatenate([tail, u_b], axis=1)       # K - 1 earlier
        c = jax.nn.silu(sum(conv[j] * seen[:, j:j + S] for j in range(K))
                        + conv_b)
        x = c[..., :H * P].reshape(B, S, H, P)
        bc = c[..., H * P:].reshape(B, S, 2, G, N)
        step = jax.nn.softplus(dt_b + lyr["ssm_dt_bias"])
        y, state, top = _ssd_block(x, step, step * rate, bc[:, :, 0],
                                   bc[:, :, 1], state, C)
        y = (y + lyr["ssm_d"][:, None] * x) * jax.nn.silu(z_b)
        # the gated norm: over groups of H / G heads, gate first
        y = _rms(y.reshape(B, S, G, -1), 1.0, cfg.norm_eps).reshape(
            B, S, H, P) * lyr["ssm_gn_g"]
        return (state, seen[:, S:], jnp.maximum(peak, top)), y

    zero = u[0, 0, 0, 0] * 0.0        # varies over the mesh as the inputs do
    start = (jnp.zeros((B, H, P, N), jnp.float32) + zero,
             jnp.zeros((B, K - 1, u.shape[-1]), jnp.float32) + zero, zero)
    (_, _, peak), y = jax.lax.scan(jax.checkpoint(block), start, (u, z, dt))
    y = y.swapaxes(0, 1).reshape(B, n * C, H, P)[:, :T]
    return jnp.einsum("bthp,hpd->btd", y, lyr["ssm_wo"]), peak


def _gated_mlp(h, lyr):
    """``(silu(h Wg) * (h Wu)) Wd`` before the model-axis reduce."""
    a = jnp.einsum("btd,df->btf", h, lyr["w_gate"])
    b = jnp.einsum("btd,df->btf", h, lyr["w_up"])
    return jnp.einsum("btf,fd->btd", jax.nn.silu(a) * b, lyr["w_down"])


def _vary(tree):
    """The tree's arrays, each marked as varying over every mesh axis any
    of them varies over (inside ``shard_map``). A custom rule's
    cotangent varies as its output does; marked so, an input that was
    broadcast over an axis takes its cotangent summed over that axis, as
    autodiff does for the implicit broadcast of a plain product."""
    every = pk._varying(*jax.tree.leaves(tree))

    def mark(a):
        more = tuple(sorted(every - jax.typeof(a).vma))
        return jax.lax.pcast(a, more, to="varying") if more else a

    return jax.tree.map(mark, tree)


def _expert_act(cfg: TxConfig, h):
    """The routed experts' activation of their up-projections ``h``:
    ``relu(u)^2``, or ``silu(g) * u`` of the pair ``(g, u)``."""
    if cfg.relu2_experts:
        return jnp.square(jax.nn.relu(h[0]))
    return jax.nn.silu(h[0]) * h[1]


def _shared(x, up, down):
    """The shared expert, relu^2 as the routed ones."""
    return jnp.square(jax.nn.relu(x @ up)) @ down


def _window_up(cfg: TxConfig, x, rows, valid, sizes, wo):
    """A window's rows of ``x`` gathered in sorted order (in the
    operands' type) and their up-projections, rows past the count set to
    0 (the grouped products leave them undefined)."""
    xs = x[rows].astype(wo["we_up"].dtype)
    keys = ("we_up",) if cfg.relu2_experts else ("we_gate", "we_up")
    return xs, keys, tuple(
        jnp.where(valid[:, None], pk.grouped_matmul(xs, wo[k], sizes), 0.0)
        for k in keys)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _expert_loop(cfg: TxConfig, x, gates, w, rows, valid, sizes, active,
                 wo):
    """The expert layer's loop: ONE ``lax.scan`` of ``n`` steps over the
    layer's token blocks and, beside them, windows of the layer's
    assignments to held experts sorted by expert. Step ``i`` runs the
    shared expert on token block ``i`` and, where window ``i`` holds
    any assignment (``active``), the routed experts over the window's
    rows: gather, grouped up-projection, activation times each row's
    gate, grouped down-projection, each row added into its token's
    output. A window past the count costs its step nothing: the turn is
    a ``cond`` on it (a batch of rows, never a ``vmap`` of them, comes
    here: under ``vmap`` the ``cond`` would run every window), so no
    loop runs inside the window loop either.

    x (N, d) float32; gates, rows, valid (n, R) a window's assignments
    in sorted order (gate, token, real); sizes (n, held) int32 a
    window's rows an expert; ``w`` the float32 leaves, ``wo`` the
    routed experts' copies in ``pk.mxu_operand_dtype``, made once a
    layer outside the loop. Differentiated by a loop of the same shape
    that rematerialises a window's forward and accumulates the weight
    gradients in float32 only in the steps whose window is active: the
    transpose of a plain scan would add every step's full gradient of
    the held experts, active or not."""
    return _expert_loop_fwd(cfg, x, gates, w, rows, valid, sizes, active,
                            wo)[0]


def _expert_loop_fwd(cfg, x, gates, w, rows, valid, sizes, active, wo):
    N = x.shape[0]
    n = rows.shape[0]
    C = N // n

    def routed(acc, rr, gg, vv, zz):
        _, _, h = _window_up(cfg, x, rr, vv, zz, wo)
        act = (_expert_act(cfg, h) * gg[:, None]).astype(
            wo["we_down"].dtype)
        y = pk.grouped_matmul(act, wo["we_down"], zz)
        return acc.at[jnp.where(vv, rr, N)].add(y, mode="drop")

    def step(acc, xs):
        i, rr, gg, vv, zz, aa = xs
        acc = jax.lax.cond(aa, partial(routed, rr=rr, gg=gg, vv=vv, zz=zz),
                           lambda a: a, acc)
        if cfg.shared_width:
            sh = _shared(jax.lax.dynamic_slice_in_dim(x, i * C, C),
                         w["sh_up"], w["sh_down"])
            acc = jax.lax.dynamic_update_slice_in_dim(
                acc, jax.lax.dynamic_slice_in_dim(acc, i * C, C) + sh,
                i * C, 0)
        return acc, None

    out, _ = jax.lax.scan(step, jnp.zeros_like(x), (
        jnp.arange(n), rows, gates, valid, sizes, active))
    return out, (x, gates, w, rows, valid, sizes, active, wo)


def _expert_loop_bwd(cfg, res, ct):
    x, gates, w, rows, valid, sizes, active, wo = res
    N = x.shape[0]
    n = rows.shape[0]
    C = N // n
    op = wo["we_down"].dtype

    def routed(carry, rr, gg, vv, zz):
        dw, dx = carry
        xs, keys, h = _window_up(cfg, x, rr, vv, zz, wo)
        act, act_vjp = jax.vjp(lambda *h: _expert_act(cfg, h), *h)
        go = jnp.where(vv[:, None], ct[rr], 0.0).astype(op)
        dga = pk.grouped_matmul(go, wo["we_down"], zz, transpose=True)
        dw = dict(dw, we_down=dw["we_down"] + pk.grouped_matmul_t(
            (act * gg[:, None]).astype(op), go, zz))
        dg = jnp.where(vv, (dga * act).sum(-1), 0.0)
        dh = act_vjp(jnp.where(vv[:, None], dga * gg[:, None], 0.0))
        dxs = 0.0
        for k, dk in zip(keys, dh):
            dk = dk.astype(op)
            dw[k] = dw[k] + pk.grouped_matmul_t(xs, dk, zz)
            dxs = dxs + pk.grouped_matmul(dk, wo[k], zz, transpose=True)
        dx = dx.at[jnp.where(vv, rr, N)].add(dxs, mode="drop")
        return (dw, dx), dg

    def step(carry, xs):
        i, rr, gg, vv, zz, aa = xs
        carry, dg = jax.lax.cond(
            aa, partial(routed, rr=rr, gg=gg, vv=vv, zz=zz),
            lambda c: (c, jnp.zeros_like(gg)), carry)
        if cfg.shared_width:
            dw, dx = carry
            _, vjp = jax.vjp(_shared, jax.lax.dynamic_slice_in_dim(
                x, i * C, C), w["sh_up"], w["sh_down"])
            dxc, du, dd = vjp(jax.lax.dynamic_slice_in_dim(ct, i * C, C))
            dw = dict(dw, sh_up=dw["sh_up"] + du, sh_down=dw["sh_down"] + dd)
            dx = jax.lax.dynamic_update_slice_in_dim(
                dx, jax.lax.dynamic_slice_in_dim(dx, i * C, C) + dxc,
                i * C, 0)
            carry = (dw, dx)
        return carry, dg

    (dw, dx), dgates = jax.lax.scan(
        step, (jax.tree.map(jnp.zeros_like, w), jnp.zeros_like(x)),
        (jnp.arange(n), rows, gates, valid, sizes, active))
    return dx, dgates, dw, None, None, None, None, None


_expert_loop.defvjp(_expert_loop_fwd, _expert_loop_bwd)


def moe_path(cfg: TxConfig, ax: Axes) -> Dict[str, Any]:
    """How ``_experts`` computes the routed experts, as a span
    attribute: ``moe_path`` ``"grouped"`` (the routed pairs only, by
    grouped products) or ``"dense"`` (every held expert over every
    token, gate-scaled: off the TPU inside a mesh program). Empty
    without experts."""
    if not cfg.n_experts:
        return {}
    return {"moe_path": "grouped" if pk.grouped_fits(any(ax)) else "dense"}


def _experts(cfg: TxConfig, ax: Axes, h, lyr):
    """The routed expert layer on the normed input ``h`` (B, T, d). Routes
    over all ``n_experts`` (softmax, top-k, renormalised; or with
    ``router_sigmoid`` sigmoid scores, the top-k by score plus the
    correction bias, the chosen scores renormalised; then times
    ``routed_scale``), and computes the part of the result that the
    experts held HERE give, for every token routed to them: no capacity
    exists and no token can be dropped. Where ``pk.grouped_fits``
    (``moe_path``) the layer's assignments to held experts are sorted by
    expert once, cut into windows of the most a token block of
    ``token_chunk`` can have, and ``_expert_loop`` runs the routed pairs
    only, through grouped products that visit only their rows' tiles.
    Otherwise the held experts run a token block at a time as one wide
    FFN whose hidden blocks are scaled by the token's gate for that
    expert (0 where it was not routed), the grouped path's oracle. A
    shared expert runs on every token block in the same loop. Returns
    ``(out before the model-axis reduce, counts (held_local,)
    assignments per held expert, [assignments routed, assignments to
    absent experts, dropped], [tile rows the grouped products visited,
    assignments they computed])``."""
    B, T, d = h.shape
    x = h.reshape(B * T, d)
    logits = jnp.einsum("nd,de->ne", x, lyr["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_sigmoid:
        scores = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(
            scores + jax.lax.stop_gradient(lyr["router_bias"]),
            cfg.experts_per_token)
        top_g = jnp.take_along_axis(scores, top_e, axis=-1)
    else:
        top_g, top_e = jax.lax.top_k(jax.nn.softmax(logits, -1),
                                     cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_g = top_g / top_g.sum(-1, keepdims=True)
    if cfg.routed_scale != 1.0:
        top_g = top_g * cfg.routed_scale
    e_loc = lyr["we_up"].shape[0]             # this model shard's experts
    ids = cfg.experts_first + _axis_index(ax.model) * e_loc + jnp.arange(e_loc)
    hit = top_e[:, :, None] == ids[None, None, :]              # (N, K, e)
    N, K = B * T, cfg.experts_per_token
    C = _chunk(N, cfg.token_chunk)
    nb = N // C

    if pk.grouped_fits(any(ax)):
        # The layer's assignments to held experts, stably sorted by held
        # expert (absent ones last, past the count), in n windows of the
        # most a token block can have: the count fits whatever the
        # routing.
        R = -(-C * min(K, e_loc) // pk.GROUP_TILE) * pk.GROUP_TILE
        local = jnp.where(hit.any(-1), top_e - ids[0], e_loc).reshape(N * K)
        order = jnp.argsort(local, stable=True)
        # argsort types its result as invariant over the mesh, whatever
        # its keys vary over: typed here as they are (and the gates'
        # source with it), the gather below takes each shard's gates
        # apart and their cotangents are summed over the shards
        top_g, order = _vary((top_g, local, order))[::2]
        if nb * R > N * K:
            order = jnp.pad(order, (0, nb * R - N * K))
        order = order[:nb * R]
        per = hit.sum((0, 1)).astype(jnp.int32)            # (e,)
        count = per.sum()
        valid = jnp.arange(nb * R) < count
        rows = jnp.where(valid, order // K, 0).reshape(nb, R)
        gates = jnp.where(valid, top_g.reshape(N * K)[
            jnp.minimum(order, N * K - 1)], 0.0).reshape(nb, R)
        valid = valid.reshape(nb, R)
        # each expert's rows [start, end) cut by each window
        end = jnp.cumsum(per)
        lo = jnp.arange(nb)[:, None] * R
        sizes = jnp.clip(jnp.minimum(end, lo + R)
                         - jnp.maximum(end - per, lo), 0).astype(jnp.int32)
        w = {k: lyr[k] for k in ("we_gate", "we_up", "we_down", "sh_up",
                                 "sh_down") if k in lyr}
        wo = {k: lyr[k].astype(pk.mxu_operand_dtype())
              for k in ("we_gate", "we_up", "we_down") if k in lyr}
        out = _expert_loop(cfg, *_vary((
            x, gates, w, rows, valid, sizes, jnp.arange(nb) * R < count,
            wo)))
        # what the windows handed the grouped products: every assignment
        applied = _psum(sizes.sum().astype(jnp.float32), ax.model)
        tiles = _psum(jnp.stack([pk.grouped_tile_rows(sizes).sum(),
                                 count]).astype(jnp.float32), ax.model)
    else:
        gate = (hit * top_g[:, :, None]).sum(1)                # (N, e)

        def part(args):
            xc, gc = args
            if cfg.relu2_experts:
                act = jnp.square(jax.nn.relu(jnp.einsum(
                    "nd,edf->nef", xc, lyr["we_up"])))
            else:
                a = jnp.einsum("nd,edf->nef", xc, lyr["we_gate"])
                b = jnp.einsum("nd,edf->nef", xc, lyr["we_up"])
                act = jax.nn.silu(a) * b
            out = jnp.einsum("nef,efd->nd", act * gc[:, :, None],
                             lyr["we_down"])
            if cfg.shared_width:
                out = out + _shared(xc, lyr["sh_up"], lyr["sh_down"])
            return out

        out = jax.lax.map(jax.checkpoint(part), (
            x.reshape(nb, C, d), gate.reshape(nb, C, e_loc)))
        applied = _psum((gate > 0).sum().astype(jnp.float32), ax.model)
        tiles = jnp.zeros(2)
    counts = hit.sum((0, 1)).astype(jnp.float32)
    here = _psum(counts.sum(), ax.model)
    routed = jnp.float32(B * T * cfg.experts_per_token)
    return out.reshape(B, T, d), counts, jnp.stack(
        [routed, routed - here, here - applied]), tiles


def _trunk(params, tokens, cfg: TxConfig, ax: Axes):
    """Embedding and the stacked blocks. tokens (B, T_local) int32 →
    ``(x (B, T_local, d), aux)``; ``aux``: ``attn`` (3,) [index loss
    summed over queries and layers, keys kept, short queries], ``moe``
    (3,) [routed, absent, dropped], ``experts`` (held_local,) counts and
    ``moe_tiles`` (2,) [tile rows visited, assignments computed], all
    summed over layers and over this shard's rows; with linear or
    Mamba-2 layers also ``state_absmax``, the largest of theirs."""
    seq_size = _axis_size(ax.seq)
    if cfg.indexer_heads and seq_size > 1:
        raise ValueError(
            f"the indexer's top-{cfg.indexer_topk} selection does not run "
            f"across a sequence axis of {seq_size}: keys chosen per query "
            "cannot ride the ring; use a mesh whose seq axis is 1")
    if cfg.has_state and seq_size > 1:
        layer = "linear layer" if "L" in cfg.pattern else "Mamba-2 layer"
        raise ValueError(
            f"the {layer}'s state does not run across a sequence axis "
            f"of {seq_size}: a shard's state would have to be handed to the "
            "next; use a mesh whose seq axis is 1")
    Tl = tokens.shape[1]
    if Tl * seq_size > cfg.max_len:
        # Caught at trace time (both values static): an out-of-range
        # position gather would silently clamp to the last row under jit.
        raise ValueError(
            f"sequence length {Tl * seq_size} exceeds max_len "
            f"{cfg.max_len}")
    pos = _axis_index(ax.seq) * Tl + jnp.arange(Tl)
    x = params["embed"][tokens]
    if "pos" in params:
        x = x + params["pos"][pos][None, :, :]
    pre = not cfg.post_norm

    def mixer(x, lyr, kind):
        """``x`` after the layer's first sublayer, and its counters."""
        g, b = {"L": ("la_ln_g", "la_ln_b"), "M": ("ssm_ln_g", "ssm_ln_b")
                }.get(kind, ("ln1_g", "ln1_b"))
        h = _norm(cfg, x, lyr[g], lyr.get(b)) if pre else x
        if kind in ("L", "M"):
            out, peak = (_linear_attention if kind == "L" else _ssm_mixer)(
                cfg, ax, h, lyr)
            aux = {"attn": jnp.zeros(3), "state_absmax": peak}
        else:
            out, attn = _attention(cfg, ax, h, lyr, pos)
            aux = {"attn": attn}
            if cfg.has_state:            # every layer reports the same keys
                aux["state_absmax"] = jnp.zeros(())
        if pre:
            return x + _psum(out, ax.model), aux       # row-parallel reduce
        return x + _norm(cfg, _psum(out, ax.model), lyr[g], lyr.get(b)), aux

    def layer_fn(x, lyr, kind="F"):
        if kind == "E":              # the expert sublayer, a layer alone
            x, aux = sublayer(x, lyr)
            return x, dict(aux, attn=jnp.zeros(3))
        x, aux = mixer(x, lyr, kind)
        if cfg.one_sublayer:
            return x, aux
        x, more = sublayer(x, lyr)
        return x, dict(aux, **more)

    def sublayer(x, lyr):
        """``x`` after the MLP or expert sublayer, and its counters."""
        h = _norm(cfg, x, lyr["ln2_g"], lyr.get("ln2_b")) if pre else x
        more = {}
        if cfg.n_experts:
            out, counts, moe, more["moe_tiles"] = _experts(cfg, ax, h, lyr)
        elif cfg.gated_width:
            out = _gated_mlp(h, lyr)
        else:
            ff = jax.nn.gelu(jnp.einsum("btd,df->btf", h, lyr["w1"])
                             + lyr["b1"])
            out = jnp.einsum("btf,fd->btd", ff, lyr["w2"])
        out = _psum(out, ax.model)
        if not pre:
            out = _norm(cfg, out + lyr.get("b2", 0.0), lyr["ln2_g"],
                        lyr.get("ln2_b"))
        x = x + out
        if pre and "b2" in lyr:
            x = x + lyr["b2"]
        if not cfg.n_experts:
            counts, moe = jnp.zeros(1), jnp.zeros(3)
        return x, {"moe": moe, "experts": counts, **more}

    if not cfg.pattern:              # every layer is the same layer
        if cfg.remat:
            layer_fn = jax.checkpoint(layer_fn)
        x, aux = jax.lax.scan(layer_fn, x, params["layers"])
        return x, _fold(aux)

    kinds = _layer_leaves(cfg)
    layer = jax.checkpoint(layer_fn, static_argnums=(2,)) if cfg.remat \
        else layer_fn

    def period_fn(x, per):
        """One period, its layers one after another: each takes its
        slice of its kind's leaves and of every layer's. (A scan over a
        run of one kind would compile that kind once, but it slices the
        stacked MLP leaves into copies: 4 GB more at the hybrid cell's
        size, which then does not fit the chip.) A counter is folded over
        the layers that report it."""
        auxes, nth = [], dict.fromkeys(kinds, 0)
        for j, kind in enumerate(cfg.pattern):
            lyr = {k: per[k][nth[kind]] for k in kinds[kind]}
            lyr.update({k: per[k][j] for k in kinds.get("*", ())})
            nth[kind] += 1
            x, aux = layer(x, lyr, kind)
            auxes.append(aux)
        return x, _fold({k: jnp.stack([a[k] for a in auxes if k in a])
                         for k in sorted(set().union(*auxes))})

    x, aux = jax.lax.scan(period_fn, x, params["layers"])
    return x, _fold(aux)


def _fold(aux: Dict[str, Any]) -> Dict[str, Any]:
    """Layers' counters, stacked on a leading axis, into one: sums, and
    the largest ``state_absmax`` of the linear and Mamba-2 layers."""
    return {k: a.max(0) if k == "state_absmax" else a.sum(0)
            for k, a in aux.items()}


def _class_logits(params, x, cfg: TxConfig, ax: Axes):
    """(B, n_classes) from the trunk's output: the mean-pool head, or
    with ``lm_head`` the last position's logits over the label tokens."""
    if not cfg.lm_head:
        pool = _psum(x.sum(axis=1), ax.seq) / (x.shape[1] * _axis_size(ax.seq))
        return pool @ params["head_w"] + params["head_b"]
    last = _norm(cfg, x[:, -1], params["lnf_g"])
    logits = jnp.einsum("bd,dc->bc", last, params["head_w"][:, :cfg.n_classes])
    if ax.seq is None:
        return logits
    # The row's last position lives on the last sequence shard.
    mine = _axis_index(ax.seq) == _axis_size(ax.seq) - 1
    return _psum(jnp.where(mine, logits, 0.0), ax.seq)


def forward_shard(params, tokens, *, cfg: TxConfig):
    """Per-shard forward (runs inside shard_map over the 3-axis mesh).

    tokens: (B_local, T_local) int32 → class logits (B_local, n_classes),
    replicated over model and seq axes."""
    x, _ = _trunk(params, tokens, cfg, MESH_AXES)
    return _class_logits(params, x, cfg, MESH_AXES)


def _next_token_loss(params, x, tokens, labels, cfg: TxConfig, ax: Axes):
    """Summed next-token cross-entropy of this shard's positions: the
    target of position t is token t+1, of a row's last position its
    label token. Logits a ``token_chunk`` of positions at a time."""
    B, Tl, d = x.shape
    nxt = labels[:, None]
    if ax.seq is not None and _axis_size(ax.seq) > 1:
        S = _axis_size(ax.seq)
        first = jax.lax.ppermute(tokens[:, :1], ax.seq,
                                 [(j, (j - 1) % S) for j in range(S)])
        nxt = jnp.where(_axis_index(ax.seq) == S - 1, nxt, first)
    targets = jnp.concatenate([tokens[:, 1:], nxt], axis=1).reshape(B * Tl)
    h = _norm(cfg, x, params["lnf_g"]).reshape(B * Tl, d)
    N = B * Tl
    C = _chunk(N, cfg.token_chunk)

    def chunk(args):
        hc, tc = args
        logp = jax.nn.log_softmax(jnp.einsum("nd,dv->nv", hc,
                                      params["head_w"]), -1)
        return -jnp.take_along_axis(logp, tc[:, None], axis=1).sum()

    return jax.lax.map(jax.checkpoint(chunk), (
        h.reshape(N // C, C, d), targets.reshape(N // C, C))).sum()


def make_loss_fn(cfg: TxConfig, mesh: Mesh, with_aux: bool = False):
    """``loss(params, tokens, labels)``; with ``with_aux`` it returns
    ``(loss, aux)``: ``aux`` has the loss's parts and the step's counters
    (see ``make_fit_step``)."""
    specs = param_specs(cfg)
    ax = MESH_AXES

    def shard_fn(params, tokens, labels):
        x, aux = _trunk(params, tokens, cfg, ax)
        rows = jax.lax.psum(jnp.float32(labels.shape[0]), DATA_AXIS)
        if cfg.lm_head:
            n_pos = rows * tokens.shape[1] * _axis_size(ax.seq)
            local = _next_token_loss(params, x, tokens, labels, cfg, ax)
            main = jax.lax.psum(local, (DATA_AXIS, SEQ_AXIS)) / n_pos
        else:
            n_pos = rows * tokens.shape[1] * _axis_size(ax.seq)
            logp = jax.nn.log_softmax(_class_logits(params, x, cfg, ax))
            local = -jnp.take_along_axis(logp, labels[:, None], axis=1).sum()
            main = jax.lax.psum(local, DATA_AXIS) / rows
        both = (DATA_AXIS, SEQ_AXIS)
        attn = jax.lax.psum(aux["attn"], both)
        index = attn[0] / n_pos
        out = {"loss_main": main, "loss_index": index,
               "keys_kept": attn[1], "queries_short": attn[2],
               "moe": jax.lax.psum(aux["moe"], both),
               "experts": jax.lax.psum(aux["experts"], both)}
        if cfg.n_experts:
            out["moe_tiles"] = jax.lax.psum(aux["moe_tiles"], both)
        if "state_absmax" in aux:
            out["state_absmax"] = jax.lax.pmax(
                jax.lax.stop_gradient(aux["state_absmax"]), ax)
        return main + index, out

    aux_specs = {"loss_main": P(), "loss_index": P(), "keys_kept": P(),
                 "queries_short": P(), "moe": P(),
                 "experts": P(MODEL_AXIS) if cfg.n_experts else P()}
    if cfg.has_state:
        aux_specs["state_absmax"] = P()
    if cfg.n_experts:
        aux_specs["moe_tiles"] = P()

    def loss_fn(params, tokens, labels):
        loss, aux = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(specs, P(DATA_AXIS, SEQ_AXIS), P(DATA_AXIS)),
            out_specs=(P(), aux_specs))(params, tokens, labels)
        return (loss, aux) if with_aux else loss

    return loss_fn


def make_train_step(cfg: TxConfig, mesh: Mesh, opt: optax.GradientTransformation):
    loss_fn = make_loss_fn(cfg, mesh)

    @jax.jit
    def train_step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step


def group_norms(grads) -> Dict[str, Any]:
    """L2 norm of the gradient per ``GRAD_GROUPS`` group; the second
    sublayer's norm counts with the sublayer it norms. Each Mamba-2 leaf
    is also reported on its own, under its name: the leaves that only
    the scan feeds (B, C, dt, A) are a small part of their group's
    norm."""
    ln2 = "mlp" if "w_gate" in grads["layers"] else "experts"
    sq: Dict[str, Any] = {}
    for name, g in list(grads["layers"].items()) + [
            (k, v) for k, v in grads.items() if k != "layers"]:
        grp = ln2 if name in ("ln2_g", "ln2_b") else GRAD_GROUPS[name]
        part = jnp.sum(jnp.square(g))
        sq[grp] = sq.get(grp, 0.0) + part
        if grp == "ssm":
            sq[name] = part
    return {k: jnp.sqrt(v) for k, v in sq.items()}


def make_fit_programs(cfg: TxConfig, mesh: Mesh,
                      opt: optax.GradientTransformation, batch: int):
    """A fit's two programs, ``(init, step)``, which the host only
    enqueues. ``init(key) -> state`` makes the weights on the mesh, each
    leaf where ``param_specs`` puts it (no host copy exists), with the
    optimizer's state: ``state = (params, opt_state, t)``.
    ``step(state, key, table, labels) -> (state, report)`` is the whole
    of a training step; its batch is drawn ON THE DEVICE: step ``t``
    takes rows ``randint(fold_in(key, t), (batch,), 0, n)`` of the
    resident token table. ``report`` stays on the device until the fit
    fetches every step's at once: the loss's parts, the gradient norm
    per group, and the step's counters."""
    loss_fn = make_loss_fn(cfg, mesh, with_aux=True)
    tok_sharding = NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))
    lab_sharding = NamedSharding(mesh, P(DATA_AXIS))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             param_specs(cfg),
                             is_leaf=lambda x: isinstance(x, P))

    @jax.jit
    def init(key):
        params = jax.lax.with_sharding_constraint(
            init_params(key, cfg), shardings)
        return params, opt.init(params), jnp.zeros((), jnp.int32)

    @partial(jax.jit, donate_argnums=(0,))
    def step(state, key, table, labels):
        params, opt_state, t = state
        sel = jax.random.randint(jax.random.fold_in(key, t), (batch,), 0,
                                 table.shape[0])
        tokens = jax.lax.with_sharding_constraint(table[sel], tok_sharding)
        labs = jax.lax.with_sharding_constraint(labels[sel], lab_sharding)
        (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, labs)
        updates, opt_state = opt.update(grads, opt_state, params)
        report = dict(aux, grad_norm=group_norms(grads))
        return (optax.apply_updates(params, updates), opt_state, t + 1), report

    return init, step


def shard_params(params, cfg: TxConfig, mesh: Mesh):
    """Place a host/param pytree on the mesh per param_specs."""
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P))


# --- the unsharded forward (predict on any topology; tests) -----------------

@partial(jax.jit, static_argnames=("cfg",))
def forward_reference(params, tokens, *, cfg: TxConfig):
    """Unsharded forward: the same block with no mesh axis — must match
    forward_shard. (B, T) int32 → class logits (B, n_classes)."""
    x, _ = _trunk(params, tokens, cfg, NO_AXES)
    return _class_logits(params, x, cfg, NO_AXES)
