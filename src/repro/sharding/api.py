"""Logical-axis sharding annotations.

Models annotate activations/params with *logical* axis names
(e.g. ("batch", "seq", "embed")).  A `Rules` mapping translates logical
names to physical mesh axes.  Outside of a mesh context the annotations
are no-ops, so the same model code runs on 1 CPU device (smoke tests)
and on the 512-chip production mesh (dry-run) unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Mapping, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class Rules:
    """Mapping from logical axis name -> physical mesh axis (or tuple).

    `bare=True` emits constraints as raw PartitionSpecs (resolved against
    the ambient abstract mesh) — required inside shard_map, where the
    context mesh carries Manual axis types that a concrete NamedSharding
    cannot match."""

    mesh: Mesh
    table: Mapping[str, Optional[object]] = field(default_factory=dict)
    bare: bool = False

    def physical(self, name: Optional[str]):
        if name is None:
            return None
        return self.table.get(name, None)


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """`jax.shard_map` with the repo's default ``check_vma=False``.

    `axis_names` is the set of *manual* axes (default: all of them);
    the remaining mesh axes stay automatic.
    """
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


_state = threading.local()


def current_rules() -> Optional[Rules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def set_rules(rules: Optional[Rules]):
    prev = current_rules()
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def spec_for(logical_axes: Sequence[Optional[str]], rules: Optional[Rules] = None) -> P:
    rules = rules or current_rules()
    if rules is None:
        return P()
    return P(*[rules.physical(a) for a in logical_axes])


def logical(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Annotate `x` with logical axes; no-op when no rules are active."""
    rules = current_rules()
    if rules is None:
        return x
    if x.ndim != len(logical_axes):
        raise ValueError(
            f"logical(): rank mismatch, array rank {x.ndim} vs axes {logical_axes}"
        )
    spec = spec_for(logical_axes, rules)
    if rules.bare:
        return jax.lax.with_sharding_constraint(x, spec)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec)
    )


# ---------------------------------------------------------------------------
# Default rule tables
# ---------------------------------------------------------------------------

def make_rules(mesh: Mesh, *, fsdp: bool = True, cfg=None,
               inside_shardmap: bool = False) -> Rules:
    """Standard 2D/3D parallelism rules, optionally architecture-aware.

    data-ish logical axes map onto the data axes (pod/data or
    pod/cluster/user for the W-HFL-refined mesh); model-ish onto "model".
    With `fsdp`, the `embed` dim of weights is sharded over the data axes
    too (ZeRO-3 style).

    When `cfg` (an ArchConfig) is given, head/KV-head/expert sharding is
    enabled only when the dimension is divisible by the model-axis size —
    forcing a 16-way constraint on 2 KV heads makes XLA fall back to full
    rematerialising reshards (observed: 28 GiB/dev instead of ~4).

    `inside_shardmap=True` produces the bare-PartitionSpec rules used in
    the manual (pod,cluster,user) context: data axes are already mapped
    manually, so batch-like names stay None and only 'model' is emitted.
    """
    axes = mesh.axis_names
    data_axes = (None if inside_shardmap else
                 tuple(a for a in ("pod", "cluster", "user", "data")
                       if a in axes) or None)
    model_ax = "model" if "model" in axes else None
    n_model = dict(zip(axes, mesh.devices.shape)).get("model", 1)
    fsdp_ax = None if (inside_shardmap or not fsdp) else data_axes

    def fits(dim: Optional[int]) -> Optional[str]:
        if dim is None:       # unknown -> assume shardable
            return model_ax
        return model_ax if (dim and dim % n_model == 0) else None

    heads_ax = kv_ax = experts_ax = model_ax
    vocab_ax = ffn_ax = model_ax
    if cfg is not None:
        heads_ax = fits(getattr(cfg, "n_heads", None) or None)
        kv_ax = fits(getattr(cfg, "n_kv_heads", None) or None)
        experts_ax = fits(getattr(cfg, "n_experts", None) or None)
        ffn_ax = fits(getattr(cfg, "d_ff", None) or None)
        vocab_ax = fits(getattr(cfg, "vocab", None) or None)
        if getattr(cfg, "family", "") in ("ssm", "hybrid"):
            # mamba head-packed dims shard iff the SSM head count divides;
            # hybrids share the logical name with attention heads, so both
            # must divide.
            d_inner = cfg.ssm_expand * cfg.d_model
            ssm_heads = d_inner // max(cfg.ssm_head_dim, 1)
            if cfg.family == "ssm":
                heads_ax = fits(ssm_heads)
            elif not (fits(ssm_heads) and heads_ax):
                heads_ax = None

    table = {
        # activations
        "batch": data_axes,
        "users": data_axes,          # stacked per-user leading dim (Mode A)
        "seq": None,
        # sequence-parallel attention (perf knob): shard the q rows over
        # 'model' when the head count cannot shard — only consistent when
        # heads are NOT also on 'model'
        "q_seq": model_ax if heads_ax is None else None,
        "embed": None,
        "heads": heads_ax,
        "kv_heads": kv_ax,
        "head_dim": None,
        "ffn": ffn_ax,
        "expert_ffn": None,
        "moe_tokens": model_ax,
        "experts": experts_ax,
        "vocab": vocab_ax,
        "state": None,
        "clusters": "pod" if "pod" in axes else None,
        # params
        "p_embed": fsdp_ax,          # fsdp'd embed dim of weight matrices
        "p_heads": heads_ax,
        "p_kv_heads": kv_ax,
        "p_ffn": ffn_ax,
        "p_expert_ffn": None,
        "p_experts": experts_ax,
        "p_vocab": vocab_ax,
        "layers": None,
    }
    return Rules(mesh=mesh, table=table, bare=inside_shardmap)


def param_sharding_tree(param_axes_tree, rules: Rules):
    """Map a pytree of logical-axes tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(rules.mesh, spec_for(axes, rules)),
        param_axes_tree,
        is_leaf=lambda v: isinstance(v, tuple),
    )
