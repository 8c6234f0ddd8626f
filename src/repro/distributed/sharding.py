"""Divisibility-aware PartitionSpec assignment for every architecture
(DESIGN.md §5).

Baseline layout:
  · dense kernels  (d_in, d_out)      -> (fsdp="data", tp="model")
  · output kernels (wo/down/out_proj) -> (tp="model", fsdp="data")
    so the contracting (heads/ffn) dim stays on "model" through a block
  · MoE expert stacks (E, …)          -> E on "model" (expert parallelism)
  · embeddings (V, d)                 -> (V→"model", d→"data")
  · batch dims                        -> ("pod", "data") jointly
  · decode KV caches: sequence dim    -> "model" (memory-safe for every
    kv-head count; see §Perf for the shard_map flash-combine upgrade)

Any dim not divisible by its mesh axis is replicated instead of erroring —
that is the honest baseline for phi3/qwen head counts; the roofline table
shows what it costs.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


shard_map = jax.shard_map
axis_size = jax.lax.axis_size


# leaf-path regex -> spec template for the TRAILING dims (leading stack dims
# get None). "F" = fsdp axis ("data"), "T" = tensor axis ("model").
_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"embed$", ("T", "F")),
    (r"lm_head$", ("F", "T")),
    # MoE expert stacks (E, d, f) / (E, f, d)
    (r"w_gate$|w_up$|w_down$", ("T", "F", None)),
    (r"router$", ("F", None)),
    # output projections: contracting dim on model
    (r"wo$|down$|out_proj$|up_out$|dt_proj$", ("T", "F")),
    # mamba/xlstm internals whose input dim is model-sharded
    (r"x_proj$", ("T", None)),
    (r"A_log$", ("T", None)),
    (r"conv_w$", (None, "T")),
    (r"w_if$", ("T", None)),
    (r"w_h$", (None, None, None)),
    # qkv biases: follow the output dim
    (r"bq$|bk$|bv$|conv_b$|D$", ("T",)),
    (r"bias$", (None,)),
    # norms replicate
    (r"ln\d?$|.*norm$", (None,)),
    # default dense kernel
    (r".*", ("F", "T")),
)


def _axis_for(tag: Optional[str], multi_pod: bool) -> Optional[str]:
    if tag == "F":
        return "data"
    if tag == "T":
        return "model"
    return None


def _mesh_axis_size(mesh, name: str) -> int:
    return dict(mesh.shape)[name]  # works for Mesh and AbstractMesh


def spec_for_leaf(path: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """PartitionSpec for one param leaf (divisibility-aware)."""
    multi_pod = "pod" in mesh.axis_names
    for pattern, template in _RULES:
        if re.search(pattern, path):
            tmpl = template
            break
    ndim = len(shape)
    t = len(tmpl)
    # leading stack dims (scan groups, expert axis already in template)
    spec = [None] * (ndim - t) + [
        _axis_for(tag, multi_pod) for tag in tmpl[max(0, t - ndim):]]
    spec = spec[:ndim]
    out = []
    for dim, ax in zip(shape, spec):
        if ax is not None and dim % _mesh_axis_size(mesh, ax) == 0:
            out.append(ax)
        else:
            out.append(None)
    return P(*out)


def _leaf_path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def param_shardings(params_shape, mesh: Mesh):
    """NamedSharding pytree matching a params shape pytree."""
    def one(path, leaf):
        return NamedSharding(mesh, spec_for_leaf(_leaf_path_str(path),
                                                 leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_spec_for(shape: Tuple[int, ...], mesh: Mesh,
                   seq_axis_dim: Optional[int] = None) -> P:
    """Shard dim0 (batch) over (pod, data) as far as divisibility allows;
    optionally shard `seq_axis_dim` over "model" (decode KV caches)."""
    axes = batch_axes(mesh)
    b = shape[0]
    use = []
    prod = 1
    for a in axes:
        s = _mesh_axis_size(mesh, a)
        if b % (prod * s) == 0:
            use.append(a)
            prod *= s
    spec = [tuple(use) if use else None] + [None] * (len(shape) - 1)
    if seq_axis_dim is not None and shape[seq_axis_dim] % \
            _mesh_axis_size(mesh, "model") == 0:
        spec[seq_axis_dim] = "model"
    return P(*spec)


def data_shardings(batch_shapes, mesh: Mesh):
    """Shardings for a train/prefill batch dict of ShapeDtypeStructs."""
    def one(leaf):
        return NamedSharding(mesh, batch_spec_for(leaf.shape, mesh))

    return jax.tree.map(one, batch_shapes)


def cache_shardings(cache_shapes, mesh: Mesh, cfg):
    """Decode-cache shardings: stacked (groups, B, S, ...) attention caches
    get S -> "model"; recurrent states get their feature dim -> "model"."""
    def one(path, leaf):
        p = _leaf_path_str(path)
        shape = leaf.shape
        name = p.split("/")[-1]
        # leading dim is the group stack; dim1 = batch
        spec = [None] * len(shape)
        bspec = batch_spec_for(shape[1:2], mesh)[0]
        spec[1] = bspec
        if name in ("k", "v", "ck", "cv") and len(shape) == 5:
            # (g, B, S, Hkv, hd): sequence-shard
            if shape[2] % _mesh_axis_size(mesh, "model") == 0:
                spec[2] = "model"
        elif name in ("ckv", "kr") and len(shape) == 4:
            if shape[2] % _mesh_axis_size(mesh, "model") == 0:
                spec[2] = "model"
        elif name == "h" and len(shape) == 4:  # mamba (g,B,di,ds)
            if shape[2] % _mesh_axis_size(mesh, "model") == 0:
                spec[2] = "model"
        elif name == "conv" and len(shape) == 4:  # (g,B,dc-1,di)
            if shape[3] % _mesh_axis_size(mesh, "model") == 0:
                spec[3] = "model"
        # xlstm C/n/m and slstm states: replicated (small, batch=1 shapes)
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# activation-sharding context: model code calls ``constrain(x, ...logical)``
# at layer boundaries; outside a launcher context it is a no-op, inside it
# pins GSPMD propagation (reshape+scan otherwise lose the batch sharding —
# measured in EXPERIMENTS.md §Perf iteration 0).
# ---------------------------------------------------------------------------

_CTX: dict = {"mesh": None, "seq_parallel": 0}


class activation_sharding:
    """Context manager: ``with activation_sharding(mesh): lower(...)``.

    seq_parallel=M: prefill/train attention additionally shards query rows
    M-way on "model" (for head counts that do not divide the TP degree —
    §Perf cell C)."""

    def __init__(self, mesh: Optional[Mesh], seq_parallel: int = 0):
        self.mesh = mesh
        self.seq_parallel = seq_parallel

    def __enter__(self):
        self._prev = (_CTX["mesh"], _CTX["seq_parallel"])
        _CTX["mesh"] = self.mesh
        _CTX["seq_parallel"] = self.seq_parallel
        return self

    def __exit__(self, *exc):
        _CTX["mesh"], _CTX["seq_parallel"] = self._prev
        return False


def ctx_seq_parallel() -> int:
    return _CTX["seq_parallel"] if _CTX["mesh"] is not None else 0


def _resolve(tag, size: int, mesh: Mesh):
    """logical tag -> mesh axis (or None), divisibility-checked."""
    if tag is None:
        return None
    if tag == "batch":
        axes = batch_axes(mesh)
        prod = 1
        use = []
        for a in axes:
            s = _mesh_axis_size(mesh, a)
            if size % (prod * s) == 0:
                use.append(a)
                prod *= s
        return tuple(use) if use else None
    # "model" (heads / ffn / experts / seq)
    if size % _mesh_axis_size(mesh, "model") == 0:
        return "model"
    return None


def constrain(x, *logical):
    """with_sharding_constraint by logical tags ("batch" | "model" | None
    per dim); no-op outside an activation_sharding context."""
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    if len(logical) != x.ndim:
        raise ValueError((logical, x.shape))
    spec = P(*[_resolve(t, d, mesh) for t, d in zip(logical, x.shape)])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))
