"""Logical-axis sharding: one rule table maps model-space axis names to mesh
axes (GSPMD style, as in MaxText/T5X).

Model code annotates tensors with *logical* axes (``batch``, ``seq``,
``heads``, ``ff``, ``experts``, ``vocab`` ...); the active ``Rules``
(a contextvar, installed by the launcher/dry-run around tracing) resolve
them to mesh axes.  With no rules installed every annotation is a no-op, so
the same model code runs single-device CPU tests and 512-chip dry-runs.

Meshes (launch/mesh.py): single-pod ``(16,16) = ("data","model")``;
multi-pod ``(2,16,16) = ("pod","data","model")``.  ``batch`` maps to
``("pod","data")`` so the pod axis shards the global batch across pods.
"""

from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["Rules", "DEFAULT_RULES", "use_rules", "current_rules", "constrain",
           "logical_to_pspec", "named_sharding"]


@dataclass
class Rules:
    """logical axis -> mesh axis (or tuple of mesh axes, or None).

    ``mesh`` (optional) carries the concrete mesh for modules that need
    explicit shard_map control (MoE expert parallelism).
    """

    table: dict = field(default_factory=dict)
    mesh: object = None

    def resolve(self, logical: str | None):
        if logical is None:
            return None
        return self.table.get(logical)

    def pspec(self, logical_axes: tuple) -> P:
        return P(*[self.resolve(a) for a in logical_axes])


def make_default_rules(multi_pod: bool = False, *, seq_shard: bool = False) -> Rules:
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    table = {
        "batch": batch_axes if len(batch_axes) > 1 else batch_axes[0],
        "batch_noshard": None,          # long-context B=1 cells
        "seq": "model" if seq_shard else None,  # SP for activations between blocks
        "act_seq": "model",             # residual-stream seq sharding (activation ZeRO)
        "embed": None,                  # d_model replicated
        "heads": "model",               # attention head dim (padded if uneven)
        "kv_heads": None,               # few KV heads (<=8) -> replicate
        "kv_seq": "model",              # decode KV-cache sequence sharding
        "ff": "model",                  # MLP hidden TP axis
        "experts": "model",             # expert parallelism
        "vocab": "model",               # embedding/logits TP
        "embed_tbl": "model",           # untied input table: d_model-sharded
        "opt": batch_axes if len(batch_axes) > 1 else batch_axes[0],  # ZeRO axis
        "fsdp": "data",                 # ZeRO-3 weight sharding (MoE experts)
        "ssm_inner": None,              # Mamba-2 runs pure-DP (see DESIGN.md)
        "lru": "model",                 # RG-LRU width TP
        # activation-side TP axes (split from the weight axes so policies
        # like FSDP can unshard activations while weights stay sharded)
        "act_heads": "model",
        "act_kv": None,
        "act_ff": "model",
        "act_vocab": "model",
        "act_lru": "model",
    }
    return Rules(table)


def make_fsdp_rules(multi_pod: bool = False, ep: bool = False) -> Rules:
    """ZeRO-3/FSDP policy (§Perf iteration 2): the batch shards over BOTH
    mesh axes (B_loc = 1 sequence per chip at train_4k), weights keep their
    model-axis shards and are all-gathered at each use by GSPMD (re-gathered
    in backward under remat).  Collective volume per step becomes ~3× the
    per-device parameter bytes instead of ~6× the activation bytes — an
    order of magnitude for the dense-train cells."""
    rules = make_default_rules(multi_pod)
    table = dict(rules.table)
    if ep:
        # MoE variant ("fsdp_ep"): the model axis keeps the experts
        # (shard_map), so the batch stays on pod×data; attention/embedding
        # weights remain model-sharded and are gathered at use (tiny vs the
        # f32 activation all-reduces they replace).
        table["batch"] = ("pod", "data") if multi_pod else "data"
    else:
        table["batch"] = (("pod", "data", "model") if multi_pod
                          else ("data", "model"))
    table["act_seq"] = None        # no TP regions -> no seq sharding needed
    for a in ("act_heads", "act_kv", "act_ff", "act_lru"):
        table[a] = None            # activations carry only the batch shard
    # loss: vocab-parallel only when the model axis is free (ep variant);
    # pure FSDP owns the model axis with the batch, so logits stay local
    table["act_vocab"] = "model" if ep else None
    rules.table = table
    return rules


def make_moe_noseq_rules(multi_pod: bool = False) -> Rules:
    """MoE train policy (§Perf iteration 6): keep TP/EP but drop the
    sequence-sharded residual.  The seq-sharded stream forces an x
    all-gather at the qkv projection AND inside the MoE shard_map every
    layer; a replicated-over-model residual (537 MB resident at qwen3-moe
    train) removes both at ~1 GB/layer wire."""
    rules = make_default_rules(multi_pod)
    table = dict(rules.table)
    table["act_seq"] = None
    rules.table = table
    return rules


def make_moe_a2a_rules(multi_pod: bool = False) -> Rules:
    """MoE train policy (§Perf iteration 7): all-to-all token dispatch in the
    expert shard_map (see models/moe._moe_a2a) instead of all-gather +
    psum-scatter of the full residual."""
    rules = make_default_rules(multi_pod)
    table = dict(rules.table)
    table["moe_dispatch"] = "a2a"
    rules.table = table
    return rules


def make_decode_kv_rules(multi_pod: bool = False) -> Rules:
    """Decode policy (§Perf iteration 3): shard KV *heads* (padded up to the
    model axis) instead of cache sequence.  Attention is then fully local
    per shard — no cache all-gather — at the cost of padded-KV cache memory
    (2× for kv=8 on a 16-way axis)."""
    rules = make_default_rules(multi_pod)
    table = dict(rules.table)
    table["kv_heads"] = "model"
    table["act_kv"] = "model"
    table["kv_seq"] = None
    rules.table = table
    return rules


DEFAULT_RULES = make_default_rules()

_active_rules: contextvars.ContextVar[Rules | None] = contextvars.ContextVar(
    "repro_sharding_rules", default=None)


@contextlib.contextmanager
def use_rules(rules: Rules | None):
    token = _active_rules.set(rules)
    try:
        yield rules
    finally:
        _active_rules.reset(token)


def current_rules() -> Rules | None:
    return _active_rules.get()


def constrain(x, logical_axes: tuple):
    """Annotate an activation with logical axes (no-op without rules)."""
    rules = current_rules()
    if rules is None:
        return x
    if x.ndim != len(logical_axes):
        raise ValueError(f"rank {x.ndim} vs logical axes {logical_axes}")
    return jax.lax.with_sharding_constraint(x, rules.pspec(logical_axes))


def logical_to_pspec(logical_axes: tuple, rules: Rules | None = None) -> P:
    rules = rules or current_rules() or DEFAULT_RULES
    return rules.pspec(logical_axes)


def named_sharding(mesh, logical_axes: tuple, rules: Rules | None = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_pspec(logical_axes, rules))


# ---------------------------------------------------------------------------
# tree-level sharding builders (used by launchers and the dry-run)
# ---------------------------------------------------------------------------

def _is_spec(s) -> bool:
    return isinstance(s, tuple)


def tree_pspecs(spec_tree, rules: Rules):
    """Logical spec tree -> PartitionSpec tree."""
    return jax.tree.map(lambda s: rules.pspec(s), spec_tree, is_leaf=_is_spec)


def tree_shardings(mesh, spec_tree, rules: Rules):
    return jax.tree.map(lambda s: NamedSharding(mesh, rules.pspec(s)),
                        spec_tree, is_leaf=_is_spec)


def zero_specs(spec_tree, shape_tree, rules: Rules, mesh, *, min_size=2**16):
    """ZeRO: give each large param's optimizer moments an extra sharded dim.

    For every leaf, find the first dimension that is (a) unsharded in the
    param spec, (b) divisible by the 'opt' rule's mesh-axis size — and shard
    it there.  Small leaves (norm scales, biases) stay as the param spec.
    Returns a logical spec tree for the fp32 moments.
    """
    opt_axes = rules.resolve("opt")
    if opt_axes is None:
        return spec_tree
    if isinstance(opt_axes, str):
        opt_axes = (opt_axes,)
    opt_axes_names = set(opt_axes)
    divisor = 1
    for a in opt_axes:
        divisor *= mesh.shape[a]

    def _axes_of(s):
        r = rules.resolve(s)
        if r is None:
            return set()
        return set(r) if isinstance(r, tuple) else {r}

    def per_leaf(spec, shape):
        import numpy as np
        if int(np.prod(shape)) < min_size:
            return spec
        used = set().union(*[_axes_of(s) for s in spec]) if spec else set()
        if used & set(opt_axes_names):
            return spec          # already sharded on the ZeRO axes (FSDP)
        new = list(spec)
        for d, (s, size) in enumerate(zip(spec, shape)):
            if s is None and size % divisor == 0:
                new[d] = "opt"
                return tuple(new)
        return spec

    return jax.tree.map(per_leaf, spec_tree, shape_tree, is_leaf=_is_spec)


def shapes_of(tree):
    return jax.tree.map(lambda x: tuple(x.shape), tree)
