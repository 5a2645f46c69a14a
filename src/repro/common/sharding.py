"""Logical-axis sharding rules (flax-partitioning style, dependency-free).

Every parameter/activation is annotated with a tuple of *logical* axis names
(e.g. ``("embed", "heads", "head_dim")``). A rules table maps logical names to
mesh axes. :func:`resolve_spec` applies the table with two safety fallbacks:

* a dimension whose size is not divisible by the mapped mesh-axis product is
  replicated instead (this is how GQA kv-heads < model-axis-size, batch=1
  long-context decode, and remainder layers degrade gracefully);
* a mesh axis is never used twice within one PartitionSpec (first dim wins).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

AxisName = Union[str, Tuple[str, ...]]
LogicalAxes = Tuple[Optional[str], ...]


def default_rules(mesh: Mesh, *, context_parallel: bool = False) -> Dict[str, AxisName]:
    """Logical-name -> mesh-axis table for the production meshes."""
    data_axes: Tuple[str, ...] = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    data: AxisName = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    rules: Dict[str, AxisName] = {
        # activations
        "batch": data,
        "seq": None,
        "act_embed": None,
        # weights — tensor-parallel over `model`; replicated over `data`.
        # (Sharding the d_model dim of weights over `data` makes GSPMD pick
        # contraction-dim-sharded matmuls with full-batch activation
        # all-reduces, about 40× the FLOP/byte at pod scale.)
        "vocab": "model",
        "embed": None,
        "embed_fsdp": data,      # MoE expert weights: too big to replicate —
                                 # stored d-sharded, explicitly all-gathered
                                 # inside the expert-parallel shard_map
        "opt_embed": data,       # optimizer moments: ZeRO — 256-way sharded
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "ffn": "model",
        "experts": "model",
        "expert_ffn": None,
        "ssm_heads": "model",
        "ssm_state": None,
        "ssm_inner": "model",
        "conv": None,
        "pred_hidden": "model",
        "bins": None,
        # kv cache
        "cache_seq": ("data" if context_parallel else None),
        "cache_kv_heads": "model",
        # scan-stacked layer axis
        "layers": None,
        "stats": None,
    }
    return rules


def resolve_spec(
    axes: LogicalAxes,
    shape: Sequence[int],
    mesh: Mesh,
    rules: Dict[str, AxisName],
) -> P:
    """Map logical axes to a PartitionSpec honoring divisibility + axis reuse."""
    assert len(axes) == len(shape), f"axes {axes} vs shape {tuple(shape)}"
    used: set = set()
    out = []
    for name, dim in zip(axes, shape):
        mapped = rules.get(name) if name is not None else None
        if mapped is None:
            out.append(None)
            continue
        axis_tuple = mapped if isinstance(mapped, tuple) else (mapped,)
        axis_tuple = tuple(a for a in axis_tuple if a in mesh.axis_names and a not in used)
        if not axis_tuple:
            out.append(None)
            continue
        prod = int(np.prod([mesh.shape[a] for a in axis_tuple]))
        if prod <= 1 or dim % prod != 0:
            # try progressively shorter prefixes before giving up
            ok = None
            for k in range(len(axis_tuple) - 1, 0, -1):
                sub = axis_tuple[:k]
                p = int(np.prod([mesh.shape[a] for a in sub]))
                if p > 1 and dim % p == 0:
                    ok = sub
                    break
            if ok is None:
                out.append(None)
                continue
            axis_tuple = ok
        used.update(axis_tuple)
        # keep the tuple form whenever the rule mapped to a tuple, even if the
        # divisibility fallback shrank it to one axis — P(("pod",)) and
        # P("pod") shard identically but compare unequal, and downstream code
        # (tests, spec equality against batch_spec) relies on stable form
        out.append(axis_tuple if isinstance(mapped, tuple) else axis_tuple[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)
