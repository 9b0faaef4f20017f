"""Composable transformer assembly for all assigned architecture families.

One ``Model`` class covers: dense decoders (GQA/MQA), MoE decoders,
pure-SSM (mamba2), hybrid attn+SSM (jamba), local:global attention
(gemma3), encoder-decoder with stub audio frontend (whisper), and
decoder with stub vision prefix (internvl2).

Layer stacking: consecutive layers with identical structure form a
*segment*; every segment's parameters are stacked on a leading dim.
Segments with >= SCAN_THRESHOLD layers run under ``lax.scan`` (compile
time stays flat for 96-layer nemotron); short segments unroll. Both use
the same per-layer code.

Streaming execution: ``param_group_specs`` partitions the parameter
tree into ordered *layer groups* keyed by param-path prefix (the embed
tables, the encoder, one group per transformer block of an unrolled
segment / one per scanned segment, the head), and ``stream_stages``
exposes the forward+loss as a walk over those groups. The streaming
FSDP runtime (``repro.dist.fsdp``) all-gathers one group at a time
through the stage walk, so its peak transient memory is O(largest
group) instead of O(model).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard
from repro.models.attention import (
    CacheSpec,
    attention_block,
    declare_attention,
    encoder_kv,
    init_kv_cache,
)
from repro.models.ffn import declare_ffn, declare_moe, ffn_block, moe_block
from repro.models.layers import (
    apply_dense,
    apply_norm,
    declare_dense,
    declare_embedding,
    declare_norm,
    sinusoidal_table,
    softmax_cross_entropy,
    unembed,
)
from repro.models.module import ParamBuilder, _fold_path, embedding_init
from repro.models.ssm import declare_mamba, init_mamba_state, mamba_block

SCAN_THRESHOLD = 8


# ---------------------------------------------------------------------------
# Layer segmentation
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str          # attn | local | global | mamba
    is_moe: bool
    count: int
    scanned: bool


@dataclasses.dataclass(frozen=True)
class PeriodicSegment:
    """A repeating heterogeneous layer pattern scanned over its repeats.

    Hybrid / local:global stacks (jamba: period 8, gemma3: period 6) have
    no long uniform runs, so plain per-kind scanning degenerates to full
    unrolling — compile time explodes at 32-96 layers on a 256-way SPMD
    partition. Instead the pattern itself becomes the scan body: params
    are stacked per position-in-period with a leading ``reps`` dim.
    """

    pattern: Tuple[Segment, ...]   # one single-layer Segment per position
    reps: int

    @property
    def count(self) -> int:
        return len(self.pattern) * self.reps

    @property
    def period(self) -> int:
        return len(self.pattern)


def _plain_segments(cfg: ModelConfig, kinds, moes, scan: bool) -> List[Segment]:
    segs: List[Segment] = []
    i = 0
    while i < len(kinds):
        kind, moe = kinds[i], moes[i]
        j = i
        while j < len(kinds) and kinds[j] == kind and moes[j] == moe:
            j += 1
        count = j - i
        segs.append(Segment(kind, moe, count,
                            scanned=scan and count >= SCAN_THRESHOLD))
        i = j
    return segs


def segment_layers(cfg: ModelConfig) -> List:
    kinds = list(cfg.layer_kinds())
    moes = [cfg.layer_is_moe(i) for i in range(cfg.num_layers)]
    plain = _plain_segments(cfg, kinds, moes, cfg.scan_layers)
    if not cfg.scan_layers:
        return plain
    if any(s.scanned for s in plain):
        return plain
    # no long uniform run: look for a repeating heterogeneous period
    pattern = list(zip(kinds, moes))
    L = len(pattern)
    for p in range(2, 13):
        reps = L // p
        if reps < 2:
            break
        if len(set(pattern[:p])) <= 1:
            # uniform period: plain segmentation already handles it
            continue
        if all(pattern[i] == pattern[i % p] for i in range(reps * p)):
            body = tuple(
                Segment(kinds[j], moes[j], 1, scanned=False) for j in range(p)
            )
            segs: List = [PeriodicSegment(pattern=body, reps=reps)]
            rem = L - reps * p
            if rem:
                segs.extend(
                    _plain_segments(
                        cfg, kinds[reps * p:], moes[reps * p:], cfg.scan_layers
                    )
                )
            return segs
    return plain


@dataclasses.dataclass(frozen=True)
class ParamGroup:
    """One layer group of the parameter tree (streaming unit).

    ``keys`` are the top-level param-path prefixes the group covers.
    Block groups of an *unrolled* segment additionally carry the layer
    index into the segment's stacked leading dim (``layer``). Scanned /
    periodic segments are one group whose every leaf carries a leading
    ``repeats`` scan dim; a scan-aware layout streams them **per scan
    iteration** (one layer row at a time) rather than as one stack-sized
    gather — ``repeats`` is the iteration count (``None`` for
    non-scanned groups)."""

    name: str
    keys: Tuple[str, ...]
    segment: Optional[int] = None     # segment index for block groups
    layer: Optional[int] = None       # layer index within an unrolled segment
    repeats: Optional[int] = None     # scan iterations for scanned groups


@dataclasses.dataclass(frozen=True)
class ScanStreamBody:
    """Scan-body view of a scanned/periodic segment for per-iteration
    streaming: ``apply_layer(x, group_view) -> (x, aux)`` advances the
    residual stream by ONE scan iteration (one block, or one full period
    for a periodic segment) given a group view holding just that
    iteration's params (leading scan dim stripped). The body recomputes
    positions from ``x`` (teacher-forced training always starts at
    position 0) and closes over static config only, so a caller may
    place it under ``jax.custom_vjp``/``lax.scan`` with a gather
    callback feeding ``group_view`` — the double-buffered prefetch path
    of ``repro.dist.fsdp``."""

    repeats: int
    apply_layer: Callable[[jax.Array, Dict[str, Any]],
                          Tuple[jax.Array, Dict[str, Any]]]


@dataclasses.dataclass(frozen=True)
class StreamStage:
    """One step of the streamed forward walk: which layer groups it
    needs (indices into ``param_group_specs()``) and how it advances the
    carry. ``apply(carry, group_trees) -> carry`` is pure; the caller
    owns materialization (all-gather) and remat boundaries, so the
    backward pass re-gathers a group instead of keeping its full-size
    view live. Stages over a scanned/periodic segment additionally
    expose ``scan`` (a :class:`ScanStreamBody`) so a scan-aware caller
    can gather one layer row per iteration instead of invoking
    ``apply`` on the whole stacked subtree; ``apply`` remains the
    stack-at-once fallback."""

    name: str
    group_ids: Tuple[int, ...]
    apply: Callable[[Dict[str, Any], Tuple[Any, ...]], Dict[str, Any]]
    scan: Optional[ScanStreamBody] = None


def _has_ffn(cfg: ModelConfig, seg: Segment) -> bool:
    return seg.is_moe or (cfg.d_ff > 0 and seg.kind != "mamba") or (
        cfg.d_ff > 0 and cfg.family == "hybrid"
    )


# ---------------------------------------------------------------------------
# Declarations
# ---------------------------------------------------------------------------
def _declare_layer(
    b: ParamBuilder, path: str, cfg: ModelConfig, seg: Segment, *, cross: bool
) -> None:
    declare_norm(b, f"{path}.norm1", cfg.d_model, cfg.norm)
    if seg.kind == "mamba":
        declare_mamba(b, f"{path}.mixer", cfg)
    else:
        declare_attention(b, f"{path}.mixer", cfg)
    if cross:
        declare_norm(b, f"{path}.norm_cross", cfg.d_model, cfg.norm)
        declare_attention(b, f"{path}.cross", cfg, cross=True)
    if _has_ffn(cfg, seg):
        declare_norm(b, f"{path}.norm2", cfg.d_model, cfg.norm)
        if seg.is_moe:
            declare_moe(b, f"{path}.ffn", cfg)
        else:
            declare_ffn(b, f"{path}.ffn", cfg.d_model, cfg.d_ff, cfg.gated_ffn)


def _stack_builder(
    cfg: ModelConfig, seg: Segment, *, cross: bool
) -> ParamBuilder:
    """Builder for ONE layer of a segment (stacked at materialization)."""
    b = ParamBuilder(param_dtype=jnp.dtype(cfg.param_dtype))
    _declare_layer(b, "layer", cfg, seg, cross=cross)
    return b


class Model:
    """Config-driven transformer. Pure functions + param pytrees."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.segments = segment_layers(cfg)
        self._enc_segment = (
            Segment("attn", False, cfg.encoder_layers, cfg.encoder_layers >= SCAN_THRESHOLD)
            if cfg.encoder_layers
            else None
        )

    # -- parameters -----------------------------------------------------------
    def init(self, key: jax.Array):
        cfg = self.cfg
        params: Dict[str, Any] = {}
        top = ParamBuilder(param_dtype=jnp.dtype(cfg.param_dtype))
        declare_embedding(top, "embed", cfg.padded_vocab, cfg.d_model)
        if not cfg.tie_embeddings:
            top.declare(
                "unembed.w", (cfg.d_model, cfg.padded_vocab), (None, "vocab"),
                init=embedding_init,
            )
        declare_norm(top, "final_norm", cfg.d_model, cfg.norm)
        if cfg.pos_embed == "learned":
            top.declare(
                "pos_embed.table", (cfg.max_position, cfg.d_model),
                (None, None), init=embedding_init,
            )
        if cfg.frontend:
            fd = cfg.frontend_dim or cfg.d_model
            declare_dense(top, "frontend_proj", fd, cfg.d_model, (None, None))
        if self._enc_segment is not None:
            declare_norm(top, "enc_final_norm", cfg.d_model, cfg.norm)
        params.update(top.init(key))

        cross = self._enc_segment is not None
        for s, seg in enumerate(self.segments):
            if isinstance(seg, PeriodicSegment):
                assert not cross, "periodic segments don't support cross-attn"
                params[f"blocks_{s}"] = {
                    f"pos_{j}": _stacked_init(
                        _stack_builder(self.cfg, sub, cross=False),
                        _fold_path(key, f"blocks_{s}_pos_{j}"), seg.reps,
                    )
                    for j, sub in enumerate(seg.pattern)
                }
            else:
                b = _stack_builder(self.cfg, seg, cross=cross)
                params[f"blocks_{s}"] = _stacked_init(
                    b, _fold_path(key, f"blocks_{s}"), seg.count
                )
        if self._enc_segment is not None:
            b = _stack_builder(self.cfg, self._enc_segment, cross=False)
            params["encoder"] = _stacked_init(
                b, _fold_path(key, "encoder"), self.cfg.encoder_layers
            )
        return params

    def logical_axes(self):
        cfg = self.cfg
        axes: Dict[str, Any] = {}
        top = ParamBuilder(param_dtype=jnp.dtype(cfg.param_dtype))
        declare_embedding(top, "embed", cfg.padded_vocab, cfg.d_model)
        if not cfg.tie_embeddings:
            top.declare(
                "unembed.w", (cfg.d_model, cfg.padded_vocab), (None, "vocab"),
                init=embedding_init,
            )
        declare_norm(top, "final_norm", cfg.d_model, cfg.norm)
        if cfg.pos_embed == "learned":
            top.declare(
                "pos_embed.table", (cfg.max_position, cfg.d_model),
                (None, None), init=embedding_init,
            )
        if cfg.frontend:
            fd = cfg.frontend_dim or cfg.d_model
            declare_dense(top, "frontend_proj", fd, cfg.d_model, (None, None))
        if self._enc_segment is not None:
            declare_norm(top, "enc_final_norm", cfg.d_model, cfg.norm)
        axes.update(top.logical_axes())
        cross = self._enc_segment is not None
        for s, seg in enumerate(self.segments):
            if isinstance(seg, PeriodicSegment):
                axes[f"blocks_{s}"] = {
                    f"pos_{j}": jax.tree.map(
                        lambda a: ("layers",) + a,
                        _stack_builder(self.cfg, sub, cross=False)
                        .logical_axes()["layer"],
                        is_leaf=lambda x: isinstance(x, tuple),
                    )
                    for j, sub in enumerate(seg.pattern)
                }
                continue
            b = _stack_builder(self.cfg, seg, cross=cross)
            axes[f"blocks_{s}"] = jax.tree.map(
                lambda a: ("layers",) + a,
                b.logical_axes()["layer"],
                is_leaf=lambda x: isinstance(x, tuple),
            )
        if self._enc_segment is not None:
            b = _stack_builder(self.cfg, self._enc_segment, cross=False)
            axes["encoder"] = jax.tree.map(
                lambda a: ("layers",) + a,
                b.logical_axes()["layer"],
                is_leaf=lambda x: isinstance(x, tuple),
            )
        return axes

    def num_params(self) -> int:
        leaves = jax.tree.leaves(jax.eval_shape(lambda: self.init(jax.random.key(0))))
        return int(sum(np.prod(leaf.shape) for leaf in leaves))

    # -- forward ----------------------------------------------------------------
    def _embed(self, params, tokens, prefix_embeddings):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(dtype)
        if cfg.family in ("dense", "moe", "hybrid", "ssm"):
            x = x * np.sqrt(cfg.d_model) if cfg.name.startswith("gemma") else x
        prefix_len = 0
        if prefix_embeddings is not None:
            proj = apply_dense(params["frontend_proj"], prefix_embeddings, dtype)
            x = jnp.concatenate([proj, x], axis=1)
            prefix_len = prefix_embeddings.shape[1]
        return shard(x, ("batch", "seq", "embed")), prefix_len

    def _positions(self, batch: int, start: int, length: int):
        pos = jnp.arange(start, start + length, dtype=jnp.int32)
        return jnp.broadcast_to(pos[None, :], (batch, length))

    def _layer_apply(
        self, p, x, seg: Segment, *, positions, cache, cache_spec,
        cross_kv, decode: bool,
    ):
        cfg = self.cfg
        aux = {"load_balance": jnp.float32(0.0), "router_z": jnp.float32(0.0)}
        # residual stream: sequence-parallel when the rules map "seq_res"
        x = shard(x, ("batch", "seq_res", "embed"))
        h = apply_norm(p["norm1"], x, cfg.norm)
        new_cache = cache
        if seg.kind == "mamba":
            y, new_cache = mamba_block(
                p["mixer"], h, cfg, state=cache, return_state=cache is not None
            )
        else:
            window = cfg.sliding_window if seg.kind == "local" else 0
            with jax.named_scope("attention"):
                y, new_cache = attention_block(
                    p["mixer"], h, cfg,
                    positions=positions, causal=True, window=window,
                    cache=cache, cache_spec=cache_spec,
                )
        x = x + y
        if cross_kv is not None:
            h = apply_norm(p["norm_cross"], x, cfg.norm)
            y, _ = attention_block(
                p["cross"], h, cfg, positions=positions, cross_kv=cross_kv,
            )
            x = x + y
        if _has_ffn(cfg, seg):
            x = shard(x, ("batch", "seq_res", "embed"))
            h = apply_norm(p["norm2"], x, cfg.norm)
            with jax.named_scope("ffn"):
                if seg.is_moe:
                    y, moe_aux = moe_block(
                        p["ffn"], h, cfg,
                        impl="einsum" if cfg.moe_num_experts <= 8 else "ragged",
                    )
                    aux = {k: aux[k] + moe_aux[k] for k in aux}
                else:
                    y = ffn_block(p["ffn"], h, cfg)
            x = x + y
        return x, new_cache, aux

    def _run_periodic(
        self, params_seg, x, seg: PeriodicSegment, *, positions, caches,
        cache_specs, decode: bool,
    ):
        """Scan over period repeats; the body applies one full period."""
        cfg = self.cfg

        def body(carry, xs):
            x = carry
            p_slice, cache_slice = xs
            aux = {"load_balance": jnp.float32(0.0),
                   "router_z": jnp.float32(0.0)}
            new_cache = {} if cache_slice is not None else None
            for j, sub in enumerate(seg.pattern):
                cache_j = None if cache_slice is None else cache_slice[f"pos_{j}"]
                spec_j = None if cache_specs is None else cache_specs[f"pos_{j}"]

                def one(p, x, cache, _sub=sub, _spec=spec_j):
                    return self._layer_apply(
                        p, x, _sub, positions=positions, cache=cache,
                        cache_spec=_spec, cross_kv=None, decode=decode,
                    )

                if cfg.remat:
                    one = jax.checkpoint(one)
                x, nc, a = one(p_slice[f"pos_{j}"], x, cache_j)
                aux = {k: aux[k] + a[k] for k in aux}
                if new_cache is not None:
                    new_cache[f"pos_{j}"] = nc
            return x, (new_cache, aux)

        xs = (params_seg, caches)
        x, (new_caches, auxs) = jax.lax.scan(body, x, xs)
        aux_total = jax.tree.map(lambda a: a.sum(), auxs)
        return x, new_caches, aux_total

    def _run_segment(
        self, params_seg, x, seg, *, positions, caches, cache_spec,
        cross_kvs, decode: bool,
    ):
        if isinstance(seg, PeriodicSegment):
            return self._run_periodic(
                params_seg, x, seg, positions=positions, caches=caches,
                cache_specs=cache_spec, decode=decode,
            )
        cfg = self.cfg
        aux_total = {"load_balance": jnp.float32(0.0), "router_z": jnp.float32(0.0)}

        def one(x, p, cache, cross_kv):
            return self._layer_apply(
                p, x, seg, positions=positions, cache=cache,
                cache_spec=cache_spec, cross_kv=cross_kv, decode=decode,
            )

        if cfg.remat:
            one = jax.checkpoint(one)

        if seg.scanned:
            def body(carry, xs):
                x = carry
                p, cache, cross_kv = xs
                x, new_cache, aux = one(x, p, cache, cross_kv)
                return x, (new_cache, aux)

            xs = (
                params_seg,
                caches,
                cross_kvs,
            )
            x, (new_caches, auxs) = jax.lax.scan(body, x, xs)
            aux_total = jax.tree.map(lambda a: a.sum(), auxs)
            return x, new_caches, aux_total
        else:
            new_caches = [] if caches is not None else None
            for i in range(seg.count):
                p_i = jax.tree.map(lambda a: a[i], params_seg)
                cache_i = (
                    None if caches is None
                    else jax.tree.map(lambda a: a[i], caches)
                )
                ckv_i = (
                    None if cross_kvs is None
                    else jax.tree.map(lambda a: a[i], cross_kvs)
                )
                x, new_cache, aux = one(x, p_i, cache_i, ckv_i)
                aux_total = {k: aux_total[k] + aux[k] for k in aux_total}
                if new_caches is not None:
                    new_caches.append(new_cache)
            if new_caches is not None:
                new_caches = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *new_caches
                )
            return x, new_caches, aux_total

    def _encode(self, params, frames):
        """Whisper-style encoder over stub frame embeddings (B, S_enc, fd)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        x = apply_dense(params["frontend_proj"], frames, dtype)
        table = sinusoidal_table(frames.shape[1], cfg.d_model)
        x = x + jnp.asarray(table, dtype)[None]
        positions = self._positions(frames.shape[0], 0, frames.shape[1])
        seg = self._enc_segment

        def one(x, p):
            h = apply_norm(p["norm1"], x, cfg.norm)
            y, _ = attention_block(
                p["mixer"], h, cfg, positions=positions, causal=False,
            )
            x = x + y
            h = apply_norm(p["norm2"], x, cfg.norm)
            return x + ffn_block(p["ffn"], h, cfg)

        if cfg.remat:
            one = jax.checkpoint(one)
        if seg.scanned:
            x, _ = jax.lax.scan(lambda c, p: (one(c, p), None), x, params["encoder"])
        else:
            for i in range(seg.count):
                x = one(x, jax.tree.map(lambda a: a[i], params["encoder"]))
        return apply_norm(params["enc_final_norm"], x, cfg.norm)

    def forward(
        self,
        params,
        tokens: jax.Array,                    # (B, S)
        *,
        prefix_embeddings: Optional[jax.Array] = None,   # vlm stub
        encoder_frames: Optional[jax.Array] = None,      # audio stub
        start_position: int = 0,
    ) -> Tuple[jax.Array, dict]:
        """Teacher-forced forward: logits over every position."""
        cfg = self.cfg
        x, prefix_len = self._embed(params, tokens, prefix_embeddings)
        B, S = x.shape[0], x.shape[1]
        positions = self._positions(B, start_position, S)
        if cfg.pos_embed == "learned":
            x = x + params["pos_embed"]["table"][positions].astype(x.dtype)
        elif cfg.pos_embed == "sinusoidal":
            table = sinusoidal_table(start_position + S, cfg.d_model)
            x = x + jnp.asarray(table, x.dtype)[positions]

        cross_kv_layers = None
        if encoder_frames is not None:
            enc_out = self._encode(params, encoder_frames)
        aux_total = {"load_balance": jnp.float32(0.0), "router_z": jnp.float32(0.0)}
        for s, seg in enumerate(self.segments):
            cross_kvs = None
            if encoder_frames is not None:
                # per-layer cross K/V from this segment's cross projections
                cross_kvs = _segment_cross_kv(
                    params[f"blocks_{s}"], enc_out, cfg
                )
            x, _, aux = self._run_segment(
                params[f"blocks_{s}"], x, seg,
                positions=positions, caches=None, cache_spec=None,
                cross_kvs=cross_kvs, decode=False,
            )
            aux_total = {k: aux_total[k] + aux[k] for k in aux_total}
        with jax.named_scope("head"):
            x = apply_norm(params["final_norm"], x, cfg.norm)
            if prefix_len:
                x = x[:, prefix_len:, :]
            logits = self._unembed(params, x)
        return logits, aux_total

    def _unembed(self, params, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        if cfg.tie_embeddings:
            logits = unembed(params["embed"], x, dtype)
        else:
            logits = apply_dense(params["unembed"], x, dtype)
        logits = shard(logits, ("batch", "seq", "vocab"))
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        if cfg.padded_vocab != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask, jnp.float32(-1e30).astype(logits.dtype), logits)
        return logits

    # -- loss -------------------------------------------------------------------
    @staticmethod
    def _combine_loss(
        logits, batch: dict, aux: dict
    ) -> Tuple[jax.Array, dict]:
        """ce + aux-regularizer objective and its metrics — the ONE
        definition of the training objective; the replicated ``loss``
        and the streamed head stage must optimize the same thing."""
        with jax.named_scope("head"):
            ce = softmax_cross_entropy(
                logits, batch["labels"], batch.get("mask"))
        total = ce + 1e-2 * aux["load_balance"] + 1e-3 * aux["router_z"]
        return total, {"ce": ce, **aux}

    def loss(
        self, params, batch: dict
    ) -> Tuple[jax.Array, dict]:
        """batch: tokens (B,S), labels (B,S), optional mask/frontend inputs."""
        logits, aux = self.forward(
            params,
            batch["tokens"],
            prefix_embeddings=batch.get("prefix_embeddings"),
            encoder_frames=batch.get("encoder_frames"),
        )
        return self._combine_loss(logits, batch, aux)

    # -- streaming (layer-grouped) execution -----------------------------------
    def param_group_specs(self) -> Tuple[ParamGroup, ...]:
        """Ordered layer groups of the param tree, by path prefix.

        Order is execution order (embed, encoder, blocks in depth order,
        head) — the gather order of the streaming FSDP step. Every
        top-level param key belongs to exactly one group; with tied
        embeddings the head *re-gathers* the embed group for the
        unembedding rather than duplicating the table into its own
        group.
        """
        cfg = self.cfg
        has_enc = self._enc_segment is not None
        groups: List[ParamGroup] = []
        embed_keys = ["embed"]
        if cfg.pos_embed == "learned":
            embed_keys.append("pos_embed")
        if cfg.frontend and not has_enc:
            embed_keys.append("frontend_proj")
        groups.append(ParamGroup("embed", tuple(embed_keys)))
        if has_enc:
            enc_keys = ["encoder", "enc_final_norm"]
            if cfg.frontend:
                enc_keys.append("frontend_proj")
            groups.append(ParamGroup("encoder", tuple(enc_keys)))
        for s, seg in enumerate(self.segments):
            key = f"blocks_{s}"
            if isinstance(seg, PeriodicSegment):
                groups.append(
                    ParamGroup(key, (key,), segment=s, repeats=seg.reps)
                )
            elif seg.scanned:
                groups.append(
                    ParamGroup(key, (key,), segment=s, repeats=seg.count)
                )
            else:
                for i in range(seg.count):
                    groups.append(
                        ParamGroup(f"{key}.{i}", (key,), segment=s, layer=i)
                    )
        head_keys = ["final_norm"]
        if not cfg.tie_embeddings:
            head_keys.append("unembed")
        groups.append(ParamGroup("head", tuple(head_keys)))
        return tuple(groups)

    def _scan_stream_body(self, seg, key: str) -> ScanStreamBody:
        """Per-iteration body of a scanned/periodic segment for the
        scan-aware streaming path. Mirrors ``_run_segment``'s scan body
        (``_run_periodic``'s for periodic segments) arithmetic op for
        op, minus caches/cross-attention (the training stream path);
        positions are recomputed from ``x`` so the body closes over
        static config only — a ``jax.custom_vjp`` boundary cannot close
        over traced values."""
        cfg = self.cfg

        if isinstance(seg, PeriodicSegment):
            def apply_period(x, view, _seg=seg):
                p_slice = view[key]
                positions = self._positions(x.shape[0], 0, x.shape[1])
                aux = {"load_balance": jnp.float32(0.0),
                       "router_z": jnp.float32(0.0)}
                for j, sub in enumerate(_seg.pattern):
                    x, _, a = self._layer_apply(
                        p_slice[f"pos_{j}"], x, sub, positions=positions,
                        cache=None, cache_spec=None, cross_kv=None,
                        decode=False,
                    )
                    aux = {k: aux[k] + a[k] for k in aux}
                return x, aux

            return ScanStreamBody(repeats=seg.reps, apply_layer=apply_period)

        def apply_layer(x, view, _seg=seg):
            positions = self._positions(x.shape[0], 0, x.shape[1])
            x, _, aux = self._layer_apply(
                view[key], x, _seg, positions=positions, cache=None,
                cache_spec=None, cross_kv=None, decode=False,
            )
            return x, aux

        return ScanStreamBody(repeats=seg.count, apply_layer=apply_layer)

    def stream_stages(self, batch: dict) -> Tuple[StreamStage, ...]:
        """The teacher-forced forward+loss as a walk over layer groups.

        Mirrors ``loss``/``forward`` arithmetic op for op: each stage
        reads only the groups it names, so a caller holding group
        buckets (``repro.dist.fsdp`` streaming mode) materializes one
        group's full-size view at a time. The carry threads
        ``batch``/``x``/``positions``/``aux`` (and ``enc_out`` for
        encoder-decoder configs) between stages. The only intentional
        deviation from ``forward``: per-layer cross-attention K/V are
        projected from the layer's own group (``forward`` vmaps the
        whole segment's projections at once) — same einsum, per layer.
        """
        cfg = self.cfg
        specs = self.param_group_specs()
        index = {g.name: i for i, g in enumerate(specs)}
        has_frames = batch.get("encoder_frames") is not None
        prefix = batch.get("prefix_embeddings")
        prefix_len = 0 if prefix is None else int(prefix.shape[1])

        def acc_aux(aux, new):
            return {k: aux[k] + new[k] for k in aux}

        def embed_apply(carry, groups):
            (top,) = groups
            b = carry["batch"]
            x, _ = self._embed(top, b["tokens"], b.get("prefix_embeddings"))
            positions = self._positions(x.shape[0], 0, x.shape[1])
            if cfg.pos_embed == "learned":
                x = x + top["pos_embed"]["table"][positions].astype(x.dtype)
            elif cfg.pos_embed == "sinusoidal":
                table = sinusoidal_table(x.shape[1], cfg.d_model)
                x = x + jnp.asarray(table, x.dtype)[positions]
            aux = {"load_balance": jnp.float32(0.0),
                   "router_z": jnp.float32(0.0)}
            return {**carry, "x": x, "positions": positions, "aux": aux}

        stages = [StreamStage("embed", (index["embed"],), embed_apply)]

        if has_frames:
            def encoder_apply(carry, groups):
                (enc,) = groups
                enc_out = self._encode(enc, carry["batch"]["encoder_frames"])
                return {**carry, "enc_out": enc_out}

            stages.append(
                StreamStage("encoder", (index["encoder"],), encoder_apply)
            )

        for g in specs:
            if g.segment is None:
                continue
            seg = self.segments[g.segment]
            if g.layer is None:
                def seg_apply(carry, groups, _g=g, _seg=seg):
                    (sub,) = groups
                    pseg = sub[_g.keys[0]]
                    cross_kvs = (
                        _segment_cross_kv(pseg, carry["enc_out"], cfg)
                        if has_frames else None
                    )
                    x, _, aux = self._run_segment(
                        pseg, carry["x"], _seg,
                        positions=carry["positions"], caches=None,
                        cache_spec=None, cross_kvs=cross_kvs, decode=False,
                    )
                    return {**carry, "x": x,
                            "aux": acc_aux(carry["aux"], aux)}

                scan_body = None
                if g.repeats is not None and not has_frames:
                    # cross-attention threads encoder K/V through the
                    # body — keep the stack-at-once fallback there
                    scan_body = self._scan_stream_body(seg, g.keys[0])
                stages.append(
                    StreamStage(
                        g.name, (index[g.name],), seg_apply, scan=scan_body
                    )
                )
            else:
                def layer_apply(carry, groups, _g=g, _seg=seg):
                    (sub,) = groups
                    p = sub[_g.keys[0]]          # one layer's tree
                    ckv = (
                        encoder_kv(p["cross"], carry["enc_out"], cfg)
                        if has_frames and "cross" in p else None
                    )
                    x, _, aux = self._layer_apply(
                        p, carry["x"], _seg,
                        positions=carry["positions"], cache=None,
                        cache_spec=None, cross_kv=ckv, decode=False,
                    )
                    return {**carry, "x": x,
                            "aux": acc_aux(carry["aux"], aux)}

                stages.append(
                    StreamStage(g.name, (index[g.name],), layer_apply)
                )

        head_ids = (index["head"],)
        if cfg.tie_embeddings:
            head_ids = head_ids + (index["embed"],)

        def head_apply(carry, groups):
            view: Dict[str, Any] = {}
            for sub in groups:
                view.update(sub)
            with jax.named_scope("head"):
                x = apply_norm(view["final_norm"], carry["x"], cfg.norm)
                if prefix_len:
                    x = x[:, prefix_len:, :]
                logits = self._unembed(view, x)
            total, metrics = self._combine_loss(
                logits, carry["batch"], carry["aux"]
            )
            return {**carry, "loss": total, "metrics": metrics}

        stages.append(StreamStage("head", head_ids, head_apply))
        return tuple(stages)

    # -- serving ------------------------------------------------------------------
    def cache_specs(self, max_len: int) -> List[CacheSpec]:
        """Per-layer cache spec; local layers get ring buffers of window size."""
        cfg = self.cfg
        specs = []
        for kind in cfg.layer_kinds():
            if kind == "local" and cfg.sliding_window:
                specs.append(
                    CacheSpec(length=min(cfg.sliding_window, max_len), ring=True)
                )
            elif kind == "mamba":
                specs.append(None)  # recurrent state instead
            else:
                specs.append(CacheSpec(length=max_len, ring=False))
        return specs

    def _one_layer_cache(self, kind, spec, batch, dtype):
        if kind == "mamba":
            return init_mamba_state(batch, self.cfg, dtype)
        return init_kv_cache(
            batch, spec, self.cfg.num_kv_heads, self.cfg.head_dim, dtype
        )

    def init_cache(self, batch: int, max_len: int):
        """Stacked per-segment caches (scan-compatible). Periodic segments
        nest caches as {pos_j: stacked-over-reps}."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        specs = self.cache_specs(max_len)
        caches = []
        li = 0
        for seg in self.segments:
            if isinstance(seg, PeriodicSegment):
                entry = {}
                for j, sub in enumerate(seg.pattern):
                    one = self._one_layer_cache(sub.kind, specs[li + j],
                                                batch, dtype)
                    entry[f"pos_{j}"] = jax.tree.map(
                        lambda a: jnp.broadcast_to(
                            a[None], (seg.reps,) + a.shape
                        ),
                        one,
                    )
                caches.append(entry)
            else:
                one = self._one_layer_cache(seg.kind, specs[li], batch, dtype)
                caches.append(
                    jax.tree.map(
                        lambda a: jnp.broadcast_to(
                            a[None], (seg.count,) + a.shape
                        ),
                        one,
                    )
                )
            li += seg.count
        return caches

    def serve_forward(
        self,
        params,
        tokens: jax.Array,                 # (B, S) prefill or (B, 1) decode
        caches,                            # from init_cache
        *,
        start_position,                    # int or traced scalar
        encoder_out: Optional[jax.Array] = None,
        prefix_embeddings: Optional[jax.Array] = None,  # vlm prefill prefix
        max_len: int,
    ):
        """One serving step: prefill (S>1) or decode (S=1)."""
        cfg = self.cfg
        x, _ = self._embed(params, tokens, prefix_embeddings)
        B, S = x.shape[0], x.shape[1]
        positions = (
            jnp.arange(S, dtype=jnp.int32)[None, :] + start_position
        )
        positions = jnp.broadcast_to(positions, (B, S))
        if cfg.pos_embed == "learned":
            x = x + params["pos_embed"]["table"][positions].astype(x.dtype)
        elif cfg.pos_embed == "sinusoidal":
            table = sinusoidal_table(cfg.max_position or max_len, cfg.d_model)
            x = x + jnp.asarray(table, x.dtype)[positions]

        specs = self.cache_specs(max_len)
        new_caches = []
        li = 0
        aux = None
        for s, seg in enumerate(self.segments):
            if isinstance(seg, PeriodicSegment):
                spec = {
                    f"pos_{j}": (None if sub.kind == "mamba" else specs[li + j])
                    for j, sub in enumerate(seg.pattern)
                }
            else:
                spec = None if seg.kind == "mamba" else specs[li]
            cross_kvs = None
            if encoder_out is not None:
                cross_kvs = _segment_cross_kv(params[f"blocks_{s}"], encoder_out, cfg)
            x, nc, _ = self._run_segment(
                params[f"blocks_{s}"], x, seg,
                positions=positions, caches=caches[s], cache_spec=spec,
                cross_kvs=cross_kvs, decode=(S == 1),
            )
            new_caches.append(nc)
            li += seg.count
        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = self._unembed(params, x[:, -1:, :])
        return logits, new_caches


def _segment_cross_kv(params_seg, enc_out, cfg: ModelConfig):
    """Stacked per-layer cross-attention K/V for one segment."""
    def per_layer(cross_p):
        return encoder_kv(cross_p, enc_out, cfg)

    return jax.vmap(per_layer)(params_seg["cross"])


def _stacked_init(builder: ParamBuilder, key: jax.Array, count: int):
    """Materialize ``count`` stacked copies of a single-layer builder."""
    keys = jax.random.split(key, count)
    stacked = jax.vmap(builder.init)(keys)
    return stacked["layer"]
