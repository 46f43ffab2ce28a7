"""Activation sharding constraints (logical-axis style).

Without constraints, XLA's SPMD partitioner may satisfy FSDP parameter
shardings by *contracting over the data-sharded weight dim* — which
replicates the batch and all-reduces full attention-score tensors (observed:
86 GB/device all-reduces on qwen3 train_4k). Pinning the residual stream to
(batch→data axes) and the wide intermediates to (feature→'model') makes the
partitioner all-gather weights instead (true FSDP) and keeps the only
activation collectives the Megatron row-parallel all-reduces.

All helpers no-op when no mesh is active (CPU unit tests) and silently drop
any axis that does not divide the corresponding dim (e.g. batch=1 in
long_500k — the cache specs then carry the parallelism).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


_DISABLED = [False]


def set_disabled(value: bool) -> None:
    """Disable all activation constraints (used by the fed dry-run, where
    local training is vmapped over the fed axis and the residual-stream
    constraints would fight the fed slicing)."""
    _DISABLED[0] = bool(value)


def _current_mesh():
    if _DISABLED[0]:
        return None
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return None
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return None
    return mesh


def _dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def constrain(x, raw_spec):
    """raw_spec: tuple per dim — None | axis-name | 'DP' (data axes) |
    tuple of axis names. Drops non-divisible/absent axes."""
    mesh = _current_mesh()
    if mesh is None or x.ndim != len(raw_spec):
        return x
    spec = []
    for dim, ax in zip(x.shape, raw_spec):
        if ax is None:
            spec.append(None)
            continue
        if ax == "DP":
            axs = _dp_axes(mesh)
        elif isinstance(ax, str):
            axs = (ax,) if ax in mesh.axis_names else ()
        else:
            axs = ()
            for a in ax:
                if a == "DP":
                    axs += _dp_axes(mesh)
                elif a in mesh.axis_names:
                    axs += (a,)
        size = 1
        for a in axs:
            size *= mesh.shape[a]
        if axs and size > 0 and dim % size == 0:
            spec.append(axs if len(axs) > 1 else axs[0])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def residual(x):
    """(B, S, D): batch over data axes, D replicated."""
    return constrain(x, ("DP", None, None))


def heads(x):
    """(B, S, H, dh): batch over data axes; heads over 'model' when they
    divide it, else sequence over 'model' (sequence-parallel attention —
    e.g. qwen3's 40 heads on a 16-wide model axis)."""
    mesh = _current_mesh()
    if mesh is None or x.ndim != 4:
        return x
    msize = mesh.shape.get("model", 1)
    if x.shape[2] % msize == 0:
        return constrain(x, ("DP", None, "model", None))
    return constrain(x, ("DP", "model", None, None))


def ffn_hidden(x):
    """(B, S, F): wide intermediate over model."""
    return constrain(x, ("DP", None, "model"))


def logits(x):
    """(B, S, V): vocab over model."""
    return constrain(x, ("DP", None, "model"))


def expert_buf(x):
    """(E, C, D): expert-parallel over model when E divides it; else
    tensor-parallel experts — capacity over the data axes."""
    mesh = _current_mesh()
    if mesh is None or x.ndim != 3:
        return x
    if x.shape[0] % mesh.shape.get("model", 1) == 0:
        return constrain(x, ("model", None, None))
    return constrain(x, (None, "DP", None))


def dp_size() -> int:
    """Number of data-parallel shards in the active mesh (1 off-mesh)."""
    mesh = _current_mesh()
    if mesh is None:
        return 1
    n = 1
    for a in _dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_size() -> int:
    """Size of the 'model' axis in the active mesh (1 off-mesh)."""
    mesh = _current_mesh()
    if mesh is None:
        return 1
    return mesh.shape.get("model", 1)


def expert_block_buf(x):
    """(E, s, C_loc, D) block-dispatched expert buffer: blocks over DP,
    experts over model when divisible."""
    mesh = _current_mesh()
    if mesh is None or x.ndim != 4:
        return x
    e_ax = "model" if x.shape[0] % mesh.shape.get("model", 1) == 0 else None
    return constrain(x, (e_ax, "DP", None, None))


def expert_block_hidden(x):
    """(E, s, C_loc, F)."""
    mesh = _current_mesh()
    if mesh is None or x.ndim != 4:
        return x
    if x.shape[0] % mesh.shape.get("model", 1) == 0:
        return constrain(x, ("model", "DP", None, None))
    return constrain(x, (None, "DP", None, "model"))


def expert_weights(w, transposed: bool = False):
    """Use-site constraint for tensor-parallel expert weights (E not
    divisible by 'model'): FSDP shard on the F dim, contraction dims
    replicated — input shardings alone are only hints to the SPMD
    partitioner; the use-site constraint is what actually stops the
    partial-sum all-reduce strategy. (E,D,F) or transposed (E,F,D)."""
    mesh = _current_mesh()
    if mesh is None or w.ndim != 3:
        return w
    if w.shape[0] % mesh.shape.get("model", 1) == 0:
        return w                       # expert-parallel path, leave alone
    spec = (None, ("model", "DP"), None) if transposed         else (None, None, ("model", "DP"))
    return constrain(w, spec)


def expert_hidden(x):
    """(E, C, F) expert intermediate: expert-parallel, or capacity×FF."""
    mesh = _current_mesh()
    if mesh is None or x.ndim != 3:
        return x
    if x.shape[0] % mesh.shape.get("model", 1) == 0:
        return constrain(x, ("model", None, None))
    return constrain(x, (None, "DP", "model"))


def ssm_state(x):
    """(B, di, ds): channels over model."""
    return constrain(x, ("DP", "model", None))
