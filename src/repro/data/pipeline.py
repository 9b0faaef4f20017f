"""Synthetic-but-structured data pipeline with per-node partitioning.

The paper evenly partitions CIFAR/PTB across worker nodes. Offline we
generate a *learnable* synthetic token stream (a seeded hidden Markov
structure — not uniform noise, so training loss meaningfully decreases
and baselines can be compared), partition it across the m decentralized
nodes (IID shards or non-IID Dirichlet skew), and emit batches shaped
(nodes, batch_per_node, seq) ready to shard over the node mesh axis.

Also provides ``input_specs``: ShapeDtypeStruct stand-ins for every
model input at the four assigned workload shapes (the dry-run consumes
these; nothing is allocated).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import InputShape, ModelConfig


# ---------------------------------------------------------------------------
# Synthetic corpus
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class SyntheticCorpus:
    """Order-1 Markov token stream: low-entropy, learnable, seeded."""

    vocab_size: int
    num_states: int = 8
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        # sparse-ish transition structure between hidden states
        self.trans = rng.dirichlet(np.full(self.num_states, 0.3),
                                   size=self.num_states)
        # each state emits from a small slice of the vocab
        self.emit_logits = rng.normal(
            size=(self.num_states, self.vocab_size)
        ) * 2.0

    def sample(
        self,
        rng: np.random.Generator,
        length: int,
        state_prior: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Sample a token stream; ``state_prior`` (num_states,) tilts the
        chain toward a node's own hidden states (start state drawn from
        it, every transition row reweighted by it) so per-node priors
        produce genuinely different stationary token distributions — the
        non-IID partition. ``None`` keeps the shared (IID) chain."""
        states = np.zeros(length, np.int64)
        if state_prior is None:
            s = rng.integers(self.num_states)
        else:
            s = rng.choice(self.num_states, p=state_prior)
        toks = np.zeros(length, np.int64)
        for t in range(length):
            states[t] = s
            p = np.exp(self.emit_logits[s] - self.emit_logits[s].max())
            p /= p.sum()
            toks[t] = rng.choice(self.vocab_size, p=p)
            trans = self.trans[s]
            if state_prior is not None:
                trans = trans * (state_prior + 1e-6)
                trans = trans / trans.sum()
            s = rng.choice(self.num_states, p=trans)
        return toks


# ---------------------------------------------------------------------------
# Decentralized partitioning
# ---------------------------------------------------------------------------
def partition_seeds(
    num_nodes: int,
    *,
    iid: bool = True,
    seed: int = 0,
    num_states: Optional[int] = None,
    concentration: float = 0.3,
):
    """Per-node stream seeds + hidden-state priors.

    Returns ``(seeds, priors)``: ``seeds`` (num_nodes,) int — one
    independent sample stream per node; ``priors`` — each node's
    distribution over the corpus's hidden Markov states. IID mode keeps
    ``priors=None`` (every node samples the shared chain — same D_i);
    non-IID mode draws one ``Dirichlet(concentration)`` vector per node
    (num_nodes, num_states), the skewed local distributions D_i the
    paper partitions with. Low concentration = strong skew.
    ``num_states`` defaults to the corpus size ``DecentralizedBatches``
    builds for the mode (8 IID / 4 non-IID).
    """
    if num_states is None:
        num_states = 8 if iid else 4
    rng = np.random.default_rng(seed)
    seeds = rng.integers(0, 2**31 - 1, size=num_nodes)
    if iid:
        return seeds, None
    priors = rng.dirichlet(
        np.full(num_states, concentration), size=num_nodes
    )
    return seeds, priors


class DecentralizedBatches:
    """Iterator of {tokens, labels} with leading (nodes, batch) dims."""

    def __init__(
        self,
        cfg: ModelConfig,
        num_nodes: int,
        batch_per_node: int,
        seq_len: int,
        *,
        iid: bool = True,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.num_nodes = num_nodes
        self.batch_per_node = batch_per_node
        self.seq_len = seq_len
        self.corpus = SyntheticCorpus(
            cfg.vocab_size, num_states=8 if iid else 4, seed=seed
        )
        seeds, priors = partition_seeds(
            num_nodes, iid=iid, seed=seed,
            num_states=self.corpus.num_states,
        )
        self.node_rngs = [np.random.default_rng(s) for s in seeds]
        self.node_priors = priors          # None for IID

    def __iter__(self) -> Iterator[Dict[str, jax.Array]]:
        return self

    def _frontend_stub(self) -> np.ndarray:
        """Per-(node, batch) stand-in embeddings, drawn fresh from each
        node's stream rng every batch (a fixed rng(0) here made every
        batch, node, and step identical — and re-generated them from
        scratch on every call)."""
        N, B = self.num_nodes, self.batch_per_node
        fd = self.cfg.frontend_dim or self.cfg.d_model
        return np.stack([
            self.node_rngs[n].normal(size=(B, self.cfg.encoder_seq, fd))
            for n in range(N)
        ])

    def __next__(self) -> Dict[str, jax.Array]:
        """The next batch. The host's sampling and the transfer to the
        device are profiler spans (``data/sample``, ``data/put``), so a
        trace of the train launcher puts a device idle gap down to one or
        the other."""
        N, B, S = self.num_nodes, self.batch_per_node, self.seq_len
        span = jax.profiler.TraceAnnotation
        with span("data/sample"):
            toks = np.zeros((N, B, S + 1), np.int32)
            for n in range(N):
                prior = (None if self.node_priors is None
                         else self.node_priors[n])
                for b in range(B):
                    toks[n, b] = self.corpus.sample(
                        self.node_rngs[n], S + 1, state_prior=prior
                    )
            stub_key = {"vision": "prefix_embeddings",
                        "audio": "encoder_frames"}.get(self.cfg.frontend)
            stub = self._frontend_stub() if stub_key else None
        with span("data/put"):
            batch = {
                "tokens": jnp.asarray(toks[..., :-1]),
                "labels": jnp.asarray(toks[..., 1:]),
            }
            if stub_key:
                batch[stub_key] = jnp.asarray(stub, dtype=jnp.bfloat16)
        return batch


# ---------------------------------------------------------------------------
# Dry-run input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------
def input_specs(
    cfg: ModelConfig,
    shape: InputShape,
    *,
    num_nodes: int = 0,          # >0: training batch with node axis
) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one workload shape, as abstract specs.

    train:    tokens/labels (nodes, per_node_batch, seq)
    prefill:  tokens (batch, seq)
    decode:   tokens (batch, 1) + KV caches are built by the serve step
    Frontend stubs ([audio]/[vlm] carve-out): precomputed embeddings of
    the right shape, bf16.
    """
    i32 = jnp.int32
    if shape.kind == "train":
        assert num_nodes > 0, "training specs need the node count"
        if shape.global_batch % num_nodes:
            raise ValueError("global batch must divide node count")
        b = shape.global_batch // num_nodes
        lead = (num_nodes, b, shape.seq_len)
        specs = {
            "tokens": jax.ShapeDtypeStruct(lead, i32),
            "labels": jax.ShapeDtypeStruct(lead, i32),
        }
        if cfg.frontend == "vision":
            specs["prefix_embeddings"] = jax.ShapeDtypeStruct(
                (num_nodes, b, cfg.encoder_seq, cfg.frontend_dim or cfg.d_model),
                jnp.bfloat16,
            )
        if cfg.frontend == "audio":
            specs["encoder_frames"] = jax.ShapeDtypeStruct(
                (num_nodes, b, cfg.encoder_seq, cfg.frontend_dim or cfg.d_model),
                jnp.bfloat16,
            )
        return specs
    if shape.kind == "prefill":
        specs = {
            "tokens": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len), i32)
        }
        if cfg.frontend == "vision":
            specs["prefix_embeddings"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.encoder_seq,
                 cfg.frontend_dim or cfg.d_model),
                jnp.bfloat16,
            )
        if cfg.frontend == "audio":
            specs["encoder_frames"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.encoder_seq,
                 cfg.frontend_dim or cfg.d_model),
                jnp.bfloat16,
            )
        return specs
    if shape.kind == "decode":
        return {
            "tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), i32),
        }
    raise ValueError(shape.kind)
