"""GSPMD sharding rules: FSDP along 'data', tensor-parallel along 'model',
pure data-parallel along 'pod' (DCN).  Rules are keyed by parameter leaf
name (we own every name; see models/*).

The quantum federated fast path adds a fourth axis, ``'clients'``: the
batched round engine's ``(C, …)`` client stacks are embarrassingly
parallel along their leading dimension (per-client independence until
the host-side aggregation — see ``core/batched_engine.py``), so the
``client_*`` helpers below shard exactly that axis across a 1-D device
mesh and replicate everything else.  Client counts that do not divide
the mesh are handled by **explicit padding** (``pad_client_count``) —
``put_client_stacks`` refuses ragged placement rather than silently
resharding.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FSDP = "data"
TP = "model"
CLIENTS = "clients"

# leaf name -> (in_axis, out_axis) for 2D weights (stacked group dim prepended
# automatically).  None = replicated on that dim.
_DENSE_RULES = {
    "wq": (FSDP, TP), "wkv": (FSDP, TP), "xwq": (FSDP, TP), "xwkv": (FSDP, TP),
    "wo": (TP, FSDP), "xwo": (TP, FSDP),
    "w_in": (FSDP, TP), "w_out": (TP, FSDP),
    "shared_w_in": (FSDP, TP), "shared_w_out": (TP, FSDP),
    "up_proj": (FSDP, TP), "down_proj": (TP, FSDP),
    "in_proj": (FSDP, TP), "out_proj": (TP, FSDP),
    "w_gates": (FSDP, TP),
    "wq_a": (FSDP, None), "wq_b": (None, TP),
    "wkv_a": (FSDP, None), "wkv_b": (None, TP),
    "router": (FSDP, None),
    "x_proj": (TP, None), "dt_w": (None, TP),
    "wk": (FSDP, TP), "wv": (FSDP, TP),
    "w_if": (TP, None),
    "embed": (TP, FSDP),          # vocab on model, d on data
    "lm_head": (FSDP, TP),        # d on data, vocab on model
    "proj_frontend": (FSDP, TP),
}

# 3D expert weights: (E, in, out)
_MOE_RULES = {"w_in": (TP, FSDP, None), "w_out": (TP, None, FSDP)}

_SPECIAL = {
    "conv_w": (None, TP),
    "A_log": (TP, None),
    "r_gates": (None, None, None),
}


def _leaf_spec(name: str, shape: Tuple[int, ...], stacked: bool) -> P:
    nd = len(shape) - (1 if stacked else 0)
    base: Tuple
    if name.endswith("__q"):
        # QLoRA packed int4: same layout as the base weight (out dim
        # halved — divisibility fitting handles the rest)
        in_ax, out_ax = _DENSE_RULES.get(name[:-3], (None, None))
        base = (in_ax, out_ax)
    elif name.endswith("__s"):
        # blockwise scales: shard the in dim like the weight
        in_ax, _ = _DENSE_RULES.get(name[:-3], (None, None))
        base = (in_ax, None)
    elif name.endswith("_lora_a"):
        tgt = name[: -len("_lora_a")]
        in_ax = _DENSE_RULES.get(tgt, (None, None))[0]
        base = (in_ax, None)
    elif name.endswith("_lora_b"):
        tgt = name[: -len("_lora_b")]
        out_ax = _DENSE_RULES.get(tgt, (None, None))[1]
        base = (None, out_ax)
    elif name in _SPECIAL and nd == len(_SPECIAL[name]):
        base = _SPECIAL[name]
    elif nd == 3 and name in _MOE_RULES:
        base = _MOE_RULES[name]
    elif nd == 2 and name in _DENSE_RULES:
        base = _DENSE_RULES[name]
    else:
        base = (None,) * nd       # norms, biases, scalars: replicated
    if stacked:
        base = (None,) + tuple(base)
    return P(*base)


def _filter_axes(spec: P, axis_names) -> P:
    """Drop mesh axes that do not exist on the current mesh."""
    def ok(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in axis_names)
            return kept if kept else None
        return e if e in axis_names else None
    return P(*(ok(e) for e in spec))


def _fit_divisibility(spec: P, shape, axis_sizes) -> P:
    """Drop sharding on dims the mesh axes do not divide evenly (e.g. a
    51866-entry vocab over a 16-way 'model' axis).  Axes are dropped from
    the right of a tuple entry until the product divides the dim."""
    if not axis_sizes:
        return spec
    out = []
    for i, e in enumerate(spec):
        if e is None:
            out.append(None)
            continue
        axes = list(e) if isinstance(e, (tuple, list)) else [e]
        while axes:
            prod = 1
            for a in axes:
                prod *= axis_sizes.get(a, 1)
            if shape[i] % prod == 0:
                break
            axes.pop()
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    return P(*out)


def param_specs(params, axis_names=("data", "model"), axis_sizes=None):
    """PartitionSpec tree matching a params pytree.

    Group-stacked subtrees live under keys 'groups' / 'enc_groups'
    (tuples of dicts of (G, ...) arrays); everything else is unstacked.
    ``axis_sizes`` (mesh.shape mapping) enables divisibility fitting.
    """
    def one(name, shape, stacked):
        s = _filter_axes(_leaf_spec(name, shape, stacked), axis_names)
        return _fit_divisibility(s, shape, axis_sizes)

    def walk(tree, stacked):
        if isinstance(tree, dict):
            return {k: (walk(v, stacked) if isinstance(v, (dict, tuple, list))
                        else one(k, v.shape, stacked))
                    for k, v in tree.items()}
        if isinstance(tree, (tuple, list)):
            return type(tree)(walk(v, stacked) for v in tree)
        raise TypeError(type(tree))

    out = {}
    for k, v in params.items():
        if k in ("groups", "enc_groups"):
            out[k] = walk(v, True)
        elif isinstance(v, (dict, tuple, list)):
            out[k] = walk(v, False)
        else:
            out[k] = one(k, v.shape, False)
    return out


def batch_axes(axis_names) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in axis_names)


def _scalar_axis(e):
    """P(('data',)) and P('data') mean the same sharding but no longer
    compare equal in jax — canonicalize 1-tuples to the bare axis name."""
    if isinstance(e, (tuple, list)) and len(e) == 1:
        return e[0]
    return e


def batch_specs(batch, axis_names, *, batch_sharded=True):
    """Spec tree for an input batch: leading dim over ('pod','data')."""
    ba = batch_axes(axis_names) if batch_sharded else ()

    def leaf(x):
        if x.ndim == 0:
            return P()
        if x.shape[0] == 1 or not ba:
            return P(*((None,) * x.ndim))
        return P(_scalar_axis(ba), *((None,) * (x.ndim - 1)))

    return jax.tree.map(leaf, batch)


def cache_specs(cache, axis_names, batch: int, axis_sizes=None):
    """Decode caches: batch over ('pod','data') when divisible, long axes
    (seq) over 'model' where present.  Divisibility-checked when
    ``axis_sizes`` (mesh.shape mapping) is given."""
    ba = batch_axes(axis_names)
    tp = TP if TP in axis_names else None

    def divides(axes, dim):
        if not axis_sizes:
            return True
        prod = 1
        for a in (axes if isinstance(axes, (tuple, list)) else [axes]):
            prod *= axis_sizes.get(a, 1)
        return dim % prod == 0

    def leaf(x):
        spec = [None] * x.ndim
        dims = list(x.shape)
        gdim = 0
        # stacked group axis first (dims[0] == n_groups, small): replicated
        if x.ndim >= 3:
            gdim = 1
        if (batch > 1 and ba and x.ndim > gdim and dims[gdim] == batch
                and divides(ba, batch)):
            spec[gdim] = _scalar_axis(ba)
        # shard the longest remaining axis on model if it's big & divisible
        rest = [(i, d) for i, d in enumerate(dims)
                if i > gdim and d >= 1024 and divides(tp, d)]
        if rest and tp:
            i, _ = max(rest, key=lambda t: t[1])
            spec[i] = tp
        return P(*spec)

    return jax.tree.map(leaf, cache)


def _mesh_sizes():
    """Axis sizes of the mesh set by ``jax.set_mesh``; empty when none is
    set — the one case the helpers below treat as "no sharding"."""
    mesh = jax.sharding.get_abstract_mesh()
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def constrain(x, spec: P):
    """with_sharding_constraint under a mesh, a no-op without one.  Axes
    that do not exist on the mesh or do not divide the dim are dropped
    (small smoke meshes)."""
    sizes = _mesh_sizes()
    if not sizes:
        return x
    fspec = _fit_divisibility(_filter_axes(spec, tuple(sizes)), x.shape,
                              sizes)
    return jax.lax.with_sharding_constraint(x, fspec)


def mesh_axis_size(name: str) -> int:
    """Size of a mesh axis under the current mesh (1 if absent)."""
    return int(_mesh_sizes().get(name, 1))


def packed_gather_spec(name: str) -> P:
    """Sharding for a QLoRA-packed weight at its use site: keep the
    'model' (TP) shard, drop the 'data' (FSDP) shard — so the FSDP
    all-gather happens on the PACKED int4 bytes (4× less wire traffic)
    and dequantization runs after the collective."""
    in_ax, out_ax = _DENSE_RULES.get(name, (None, None))
    keep = lambda ax: ax if ax == TP else None
    return P(keep(in_ax), keep(out_ax))


def head_axis_choice(KH: int, G: int) -> tuple:
    """For grouped-attention tensors laid out (..., KH, G, ...): which of
    the two head dims can carry the 'model' axis?  Returns (kh_axis,
    g_axis) — exactly one is 'model' when divisible, favoring KH."""
    tp = mesh_axis_size(TP)
    if tp <= 1:
        return (None, None)
    if KH % tp == 0:
        return (TP, None)
    if G % tp == 0:
        return (None, TP)
    return (None, None)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# 'clients' axis — the batched federated round engine's mesh dimension
# ---------------------------------------------------------------------------
def client_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices, axis
    ``'clients'``.  ``None`` → all visible devices.  Raises when more
    devices are requested than the platform exposes (force host devices
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if n < 1:
        raise ValueError(f"n_devices must be >= 1, got {n}")
    if n > len(devs):
        raise ValueError(
            f"client mesh wants {n} devices but only {len(devs)} are "
            f"visible; set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={n} (before jax initializes) or lower n_devices")
    return Mesh(np.asarray(devs[:n]), (CLIENTS,))


def pad_client_count(n_clients: int, n_shards: int) -> int:
    """Smallest multiple of ``n_shards`` that is >= ``n_clients`` — the
    padded leading dim of the client stacks.  Padding clients are inert:
    all-zero masks and zero iteration budgets (see the engine's padding
    contract), so they never contribute to losses or aggregation."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    return -(-int(n_clients) // int(n_shards)) * int(n_shards)


def check_client_divisibility(n_clients: int, n_shards: int) -> None:
    """Ragged client axes are an error, not an implicit reshard: pad
    first with ``pad_client_count`` (the engine does this at
    construction) or shrink the mesh."""
    if n_clients % n_shards != 0:
        raise ValueError(
            f"client axis of size {n_clients} does not divide across "
            f"{n_shards} mesh shards; pad to "
            f"{pad_client_count(n_clients, n_shards)} with inert clients "
            f"(pad_client_count) or use a mesh whose 'clients' axis "
            f"divides {n_clients}")


def client_stack_spec(ndim: int) -> P:
    """Spec for a client-stacked array: leading dim on 'clients', the
    rest replicated — (C, Bmax, F) → P('clients', None, None), etc."""
    if ndim < 1:
        return P()
    return P(CLIENTS, *((None,) * (ndim - 1)))


def client_specs(arrays, n_clients: int):
    """Spec tree for a pytree of engine inputs: leaves whose leading dim
    equals ``n_clients`` ride the 'clients' axis, everything else (θ_g,
    scalars) is replicated."""
    def leaf(x):
        if getattr(x, "ndim", 0) >= 1 and x.shape[0] == n_clients:
            return client_stack_spec(x.ndim)
        return P()
    return jax.tree.map(leaf, arrays)


def client_tree_specs(tree, n_clients: int):
    """Spec tree for a **client-stacked pytree** — LoRA adapter stacks,
    vmapped AdamW states: every array leaf must carry the client axis
    leading (``(C, …)``), and a leaf that does not is an error, not a
    silent replication.  (``client_specs`` is the permissive variant for
    mixed input bundles where θ_g-like leaves are legitimately
    replicated; for an adapter stack a non-client leaf means someone
    forgot to vmap the init.)"""
    def leaf(x):
        if getattr(x, "ndim", 0) < 1 or x.shape[0] != n_clients:
            raise ValueError(
                f"client-stacked pytree leaf has shape "
                f"{getattr(x, 'shape', ())}, expected leading dim "
                f"{n_clients}; stack per-client state with jax.vmap "
                f"before placement")
        return client_stack_spec(x.ndim)
    return jax.tree.map(leaf, tree)


def put_client_tree(mesh: Mesh, tree, n_clients: int):
    """Place a client-stacked pytree (adapters / optimizer states) on the
    'clients' mesh — strict: every leaf sharded along its leading client
    axis (``client_tree_specs``)."""
    check_client_divisibility(n_clients, mesh.shape[CLIENTS])
    specs = client_tree_specs(tree, n_clients)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, specs)


def put_replicated(mesh: Mesh, x):
    """Explicitly replicate an array (or pytree — e.g. the frozen LLM
    base) on every mesh device — for inputs like θ_g whose leading dim
    could coincidentally equal the padded client count (shape inference
    must never shard them)."""
    return jax.tree.map(
        lambda v: jax.device_put(v, NamedSharding(mesh, P())), x)


def put_client_stacks(mesh: Mesh, arrays, n_clients: int):
    """Place a pytree of engine inputs on ``mesh``: client-stacked leaves
    sharded along 'clients', the rest replicated.  The jitted round
    program then partitions along the client axis by computation-follows-
    data — no in_shardings plumbing at every call site.

    Population stacks (the fused driver's ``(C_pop, …)`` parameter /
    budget / loss arrays, C_pop ≫ the per-round cohort) place through
    this same helper: the population axis IS the client axis, padded
    with ``pad_client_count`` like any other ragged client count.  The
    round cohort gathered *from* them inside the fused program needs
    ``constrain_client_axis`` — see below."""
    check_client_divisibility(n_clients, mesh.shape[CLIENTS])
    specs = client_specs(arrays, n_clients)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        arrays, specs)


def constrain_replicated(x, mesh: Optional[Mesh]):
    """Pin a traced array to full replication inside a jitted program;
    no-op when ``mesh is None``.  The fused population driver keeps its
    ``(C_pop, …)`` carry arrays replicated (see the placement tradeoff
    in ``core/fused_rounds.py``), and a scatter of sharded per-cohort
    values into them would otherwise let GSPMD pick an output sharding
    that drifts between scan iterations."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def constrain_client_axis(x, mesh: Optional[Mesh]):
    """Pin a **traced** client-stacked array to the 'clients' axis inside
    a jitted program (``with_sharding_constraint``); no-op when
    ``mesh is None`` (the single-device path).

    Computation-follows-data covers arrays that enter the program with a
    placement, but the fused round driver *gathers* its per-round cohort
    stacks out of the ``(C_pop, …)`` population by traced indices — a
    dynamic gather whose output sharding GSPMD is free to resolve as
    replicated, which would serialize the whole local phase on one
    device.  Constraining the gathered ``(C_round, …)`` stacks (leading
    dim on 'clients', rest replicated, i.e. ``client_stack_spec``)
    restores the per-client partitioning the round program is built
    around.  ``C_round`` must divide the mesh — the fused driver
    enforces that at construction."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, client_stack_spec(getattr(x, "ndim", 0))))
