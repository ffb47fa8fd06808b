"""Pass 7 — SPMD: collective-safety lint on a virtual 8-device mesh.

GSPMD "fixes" a missing sharding annotation by inserting collectives:
an accidental all-gather silently replicates a sharded tensor (HBM and
ICI paid per step, no error anywhere), and asymmetric collective
sequences across branches deadlock a real mesh while running fine on
one host. Both are CPU-detectable: the repo's distributed surfaces
(mp_layers column/row linears, ring attention, the MoE EP exchange)
are dry-traced and XLA-compiled on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8`` — the same fake-device
trick tests/conftest.py uses), and the partitioned HLO + jaxpr are
linted:

- ``S-GATHER``: a collective kind (``all-gather`` / ``all-reduce`` /
  ``all-to-all`` / ``collective-permute`` / ``reduce-scatter``) in the
  partitioned HLO that the site did not declare — the signature of a
  dropped sharding constraint (GSPMD gathered to replicate).
- ``S-MATCH``: ``lax.cond``/``switch`` branches inside a traced
  program whose collective sequences differ (primitive + axis) — on a
  real mesh a data-dependent branch picking different collectives per
  device is a deadlock; CPU runs never notice.
- ``S-UNSPEC``: a site that declares its outputs sharded
  (``expects_constraint``) but whose trace carries no
  ``with_sharding_constraint`` (and no shard_map, which fixes output
  layout via ``out_specs``) — GSPMD is free to replicate the output.

Sites are skipped (not failed) when fewer than 8 CPU devices exist —
the virtual mesh needs the XLA flag set before backend init (the
tpu_lint CLI and tests/conftest.py both set it).
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Callable, List, Optional, Tuple

from .base import Finding, waive_from_sources
from .jaxpr_util import repo_root, sub_jaxprs

__all__ = ["SpmdSite", "SPMD_SITES", "virtual_mesh", "mesh_available",
           "hlo_collective_counts", "check_spmd_site", "run_spmd_pass",
           "VIRTUAL_MESH_DEVICES", "trace_census"]

#: devices the virtual CPU mesh needs (matches tests/conftest.py)
VIRTUAL_MESH_DEVICES = 8

_HLO_COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|all-to-all|collective-permute|"
    r"reduce-scatter)\b")

#: jaxpr-level collective primitives (for the branch-symmetry check)
_COLLECTIVE_PRIMS = ("psum", "pmax", "pmin", "ppermute", "pgather",
                     "all_to_all", "all_gather", "reduce_scatter")

#: what ``lax.psum`` / ``lax.all_gather`` trace to inside a shard_map
#: that checks varying-ness (the default): same wire collective, so the
#: census reports it under the plain name
_VMA_ALIASES = {"psum_invariant": "psum",
                "all_gather_invariant": "all_gather"}


@dataclasses.dataclass
class SpmdSite:
    name: str                 # "mp.column_row_linear", ...
    build: Callable           # () -> (fn, args) — args committed arrays
    allowed: frozenset        # HLO collective kinds the source declares
    expects_constraint: bool = False
    path: str = ""
    line: int = 0

    def __post_init__(self):
        import os

        code = getattr(self.build, "__code__", None)
        if code is not None and not self.path:
            repo = repo_root()
            fname = code.co_filename
            self.path = os.path.relpath(fname, repo) \
                if fname.startswith(repo) else fname
            self.line = code.co_firstlineno


def mesh_available() -> bool:
    import jax

    try:
        return len(jax.devices("cpu")) >= VIRTUAL_MESH_DEVICES
    except Exception:
        return False


def virtual_mesh(shape: Tuple[int, ...] = (VIRTUAL_MESH_DEVICES,),
                 names: Tuple[str, ...] = ("x",)):
    """A jax Mesh over the virtual CPU devices, or None when the
    process was started without the fake-device XLA flag."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    if not mesh_available():
        return None
    devs = jax.devices("cpu")[:VIRTUAL_MESH_DEVICES]
    return Mesh(np.array(devs).reshape(shape), names)


def hlo_collective_counts(hlo_text: str) -> Counter:
    """collective kind -> occurrence count in partitioned HLO text."""
    return Counter(_HLO_COLLECTIVE_RE.findall(hlo_text))


# ----------------------------------------------------------- jaxpr checks

def _collective_seq(jaxpr) -> List[Tuple[str, str]]:
    """Flat (primitive, axes) sequence of a jaxpr incl. sub-jaxprs —
    order matters: it is the device's collective schedule."""
    seq: List[Tuple[str, str]] = []
    for eqn in jaxpr.eqns:
        prim = _VMA_ALIASES.get(eqn.primitive.name, eqn.primitive.name)
        if prim in _COLLECTIVE_PRIMS:
            axes = eqn.params.get("axes", eqn.params.get("axis_name"))
            seq.append((prim, str(axes)))
        for sj in sub_jaxprs(eqn):
            seq += _collective_seq(sj)
    return seq


def trace_census(fn, *args) -> List[Tuple[str, str]]:
    """The traced collective census of ``fn(*args)``: the ordered
    (primitive, axes) sequence of every collective in the jaxpr,
    sub-jaxprs included — a loop body's collectives appear ONCE (the
    body is traced once), so a fori_loop decode layer contributes its
    per-layer sequence exactly once. The shared helper behind the
    census pins in test_tp_serving, test_moe_ep_decode, the dryrun
    multichip/overlap phases, and the S-OVERLAP lint."""
    import jax

    return _collective_seq(jax.make_jaxpr(fn)(*args).jaxpr)


def _check_branch_symmetry(jaxpr, site, findings):
    from jax.extend.core import ClosedJaxpr

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            branches = [b.jaxpr if isinstance(b, ClosedJaxpr) else b
                        for b in eqn.params.get("branches", ())]
            seqs = [_collective_seq(b) for b in branches]
            if len({tuple(s) for s in seqs}) > 1:
                findings.append(Finding(
                    rule="S-MATCH", site=site.name, path=site.path,
                    line=site.line,
                    message=(f"cond branches in `{site.name}` issue "
                             f"different collective sequences {seqs} — "
                             "devices taking different branches "
                             "deadlock the mesh; hoist the collectives "
                             "out of the branch bodies")))
        for sj in sub_jaxprs(eqn):
            _check_branch_symmetry(sj, site, findings)


def _has_prim(jaxpr, names) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            return True
        for sj in sub_jaxprs(eqn):
            if _has_prim(sj, names):
                return True
    return False


# ------------------------------------------------------------- site check

def check_spmd_site(site: SpmdSite) -> List[Finding]:
    """Trace + partition one site on the virtual mesh and lint it."""
    import jax

    findings: List[Finding] = []
    fn, args = site.build()
    closed = jax.make_jaxpr(fn)(*args)

    _check_branch_symmetry(closed.jaxpr, site, findings)

    if site.expects_constraint and not _has_prim(
            closed.jaxpr, ("sharding_constraint", "shard_map")):
        findings.append(Finding(
            rule="S-UNSPEC", site=site.name, path=site.path,
            line=site.line,
            message=(f"`{site.name}` declares sharded outputs but the "
                     "trace has no with_sharding_constraint (and no "
                     "shard_map out_specs) — GSPMD may replicate the "
                     "output (all-gather per step)")))

    hlo = jax.jit(fn).lower(*args).compile().as_text()
    for kind, n in sorted(hlo_collective_counts(hlo).items()):
        if kind in site.allowed:
            continue
        findings.append(Finding(
            rule="S-GATHER", site=site.name, path=site.path,
            line=site.line,
            message=(f"partitioned HLO of `{site.name}` contains {n} "
                     f"undeclared `{kind}` op(s) (declared: "
                     f"{sorted(site.allowed) or 'none'}) — GSPMD "
                     "inserted it to repair a missing sharding "
                     "annotation; add the with_sharding_constraint "
                     "(or declare the collective at the site)")))
    return findings


# ------------------------------------------------------------ repo sites

def _fleet_mesh_2x4():
    """The dp4 x mp2 hybrid mesh via fleet.init — the same global-state
    setup the distributed tests use."""
    from ..distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        **strategy.hybrid_configs,
        "dp_degree": 4, "mp_degree": 2, "pp_degree": 1,
        "sharding_degree": 1, "sep_degree": 1,
    }
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group().mesh


def _build_mp_linear():
    """Column-parallel -> row-parallel linear pair (fleet mpu layers):
    the contraction over the mp-sharded dim must lower to exactly one
    all-reduce; output pinned dp-sharded via with_sharding_constraint."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..core import engine as ce
    from ..core.tensor import Tensor
    from ..distributed.fleet.layers.mpu.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)
    from ..nn import functional as F

    mesh = _fleet_mesh_2x4()
    col = ColumnParallelLinear(32, 64, gather_output=False)
    row = RowParallelLinear(64, 32, input_is_parallel=True)
    jmesh = mesh.jax_mesh()
    out_sharding = NamedSharding(jmesh, P("dp", None))

    def fn(xa, wc, bc, wr, br):
        with ce.no_grad():
            h = F.relu(F.linear(Tensor(xa), Tensor(wc), Tensor(bc)))
            y = F.linear(h, Tensor(wr), Tensor(br))
        return jax.lax.with_sharding_constraint(y._data, out_sharding)

    x = jax.device_put(jnp.ones((8, 32), jnp.float32),
                       NamedSharding(jmesh, P("dp", None)))
    return fn, (x, col.weight._data, col.bias._data, row.weight._data,
                row.bias._data)


def _build_ring_attention():
    """The ring-attention shard_map body: K/V rotate via ppermute only —
    any all-gather here means the seq sharding got dropped."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..nn.functional import ring_attention as ra

    mesh = virtual_mesh((VIRTUAL_MESH_DEVICES,), ("sep",))
    body = functools.partial(
        ra._ring_attention_sharded, axis_name="sep", causal=True,
        scale=8.0 ** -0.5, axis_size=VIRTUAL_MESH_DEVICES)
    pspec = P(None, "sep", None, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(pspec,) * 3,
                       out_specs=pspec)
    q = jax.device_put(
        jnp.ones((1, 2 * VIRTUAL_MESH_DEVICES, 2, 8), jnp.float32),
        NamedSharding(mesh, pspec))
    return fn, (q, q, q)


def _build_moe_ep():
    """The MoE expert-parallel exchange: dispatch/combine must stay two
    all-to-alls (plus the aux/drop psum) — a reduce-formulated exchange
    or a gather means the EP sharding broke."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from ..core import engine as ce
    from ..core.tensor import Tensor
    from ..incubate.moe import MoELayer

    mesh = virtual_mesh((VIRTUAL_MESH_DEVICES,), ("x",))
    paddle.seed(0)
    moe = MoELayer(d_model=16, num_experts=8, gate="gshard",
                   d_hidden=32, capacity_factor=2.0, ep_mesh=(mesh, "x"))

    def fn(xa):
        with ce.no_grad():
            return moe(Tensor(xa))._data

    x = jax.device_put(jnp.ones((8, 4, 16), jnp.float32),
                       NamedSharding(mesh, P("x", None, None)))
    return fn, (x,)


def _build_moe_ep_decode():
    """The ep2 expert-parallel MoE decode step (ISSUE 15): the only
    collectives its partitioned HLO may carry are the per-MoE-layer
    all-to-all dispatch/combine PAIR plus the replicated-hidden
    all-gather — a reduce-formulated exchange or an extra gather means
    the expert-bank sharding broke."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..distributed.tp import TPContext, serving_mesh
    from ..incubate.nn.fused_transformer import (FusedMultiTransformer,
                                                 PagedKV, rope_table)
    from ..inference.kv_cache import BlockKVCacheManager

    paddle.seed(0)
    st = FusedMultiTransformer(32, 4, 64, 2, num_kv_heads=2,
                               max_position=64, moe_num_experts=4,
                               moe_top_k=2)
    tp = TPContext.create(
        st.num_heads, st.num_kv_heads, st.head_dim,
        mesh=serving_mesh(2, devices=jax.devices("cpu")[:2],
                          axis="ep"))
    w_tp = tp.shard_stack(st._stack())
    mgr = BlockKVCacheManager(st.num_layers, st.num_kv_heads,
                              st.head_dim, page_size=4, num_pages=16,
                              reserve_scratch=True, mp_degree=tp.mp,
                              mesh=tp.mesh)
    for i in range(2):
        mgr.allocate(i, 8)
    tables = mgr.block_tables(range(2), 4)
    cache = mgr.fresh_cache()
    cos, sin = rope_table(64, st.head_dim)
    lens = jnp.array([6, 6], jnp.int32)
    x = jnp.ones((2, st.embed_dim), jnp.float32)

    def fn(w, xb, ck, cv):
        h, cache2 = st.decode_raw(w, xb, PagedKV(ck, cv), tables,
                                  lens, cos, sin, tp=tp)
        return h, cache2.k, cache2.v

    return fn, (w_tp, x, cache.k, cache.v)


def _tp_serving_setup():
    """Shared builder state for the TP serving sites: a tiny
    FusedMultiTransformer, its shard-at-load mp2 stacks, and a
    kv-head-sharded pool over two of the virtual devices."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..distributed.tp import TPContext, serving_mesh
    from ..incubate.nn.fused_transformer import (FusedMultiTransformer,
                                                 rope_table)
    from ..inference.kv_cache import BlockKVCacheManager

    paddle.seed(0)
    st = FusedMultiTransformer(32, 4, 64, 2, num_kv_heads=2,
                               max_position=64)
    tp = TPContext.create(
        st.num_heads, st.num_kv_heads, st.head_dim,
        mesh=serving_mesh(2, devices=jax.devices("cpu")[:2]))
    w_tp = tp.shard_stack(st._stack())
    mgr = BlockKVCacheManager(st.num_layers, st.num_kv_heads,
                              st.head_dim, page_size=4, num_pages=16,
                              reserve_scratch=True, mp_degree=tp.mp,
                              mesh=tp.mesh)
    for i in range(2):
        mgr.allocate(i, 8)
    tables = mgr.block_tables(range(2), 4)
    cache = mgr.fresh_cache()
    cos, sin = rope_table(64, st.head_dim)
    lens = jnp.array([6, 6], jnp.int32)
    return st, tp, w_tp, cache, tables, cos, sin, lens


def _build_tp_decode():
    """The mp2 tensor-parallel decode step: the ONLY collectives the
    partitioned HLO may carry are the per-layer psum pair (all-reduce
    after the row-parallel O-proj and FFN2 — the reference's
    fused_multi_transformer_op.cu:220,529 ring_id points); a gather
    here means a weight/pool sharding annotation got dropped."""
    import jax.numpy as jnp

    from ..incubate.nn.fused_transformer import PagedKV

    st, tp, w_tp, cache, tables, cos, sin, lens = _tp_serving_setup()
    x = jnp.ones((2, st.embed_dim), jnp.float32)

    def fn(w, xb, ck, cv):
        h, cache2 = st.decode_raw(w, xb, PagedKV(ck, cv), tables,
                                  lens, cos, sin, tp=tp)
        return h, cache2.k, cache2.v

    return fn, (w_tp, x, cache.k, cache.v)


def _build_tp_prefill_chunk():
    """The mp2 chunked-prefill program: same psum-only contract as the
    decode site (the chunk attends to cached pages + its causal
    triangle entirely shard-locally)."""
    import jax.numpy as jnp

    from ..incubate.nn.fused_transformer import PagedKV

    st, tp, w_tp, cache, tables, cos, sin, _l = _tp_serving_setup()
    x = jnp.ones((2, 4, st.embed_dim), jnp.float32)
    start = jnp.zeros((2,), jnp.int32)
    clens = jnp.full((2,), 4, jnp.int32)

    def fn(w, xb, ck, cv):
        h, cache2 = st.prefill_chunk_raw(
            w, xb, PagedKV(ck, cv), tables, start, clens, cos, sin,
            tp=tp)
        return h, cache2.k, cache2.v

    return fn, (w_tp, x, cache.k, cache.v)


def _build_tp_decode_ring():
    """The mp2 decode step under ``overlap="ring"`` (ISSUE 19): the
    row-parallel reductions pipeline as chunked ppermute rings, so the
    partitioned HLO may carry collective-permutes ONLY — an all-reduce
    here means a site bypassed the overlap knob (a stray blocking
    psum), a gather means a sharding annotation dropped."""
    import jax.numpy as jnp

    from ..incubate.nn.fused_transformer import PagedKV

    st, tp, w_tp, cache, tables, cos, sin, lens = _tp_serving_setup()
    x = jnp.ones((2, st.embed_dim), jnp.float32)

    def fn(w, xb, ck, cv):
        h, cache2 = st.decode_raw(w, xb, PagedKV(ck, cv), tables,
                                  lens, cos, sin, tp=tp,
                                  overlap="ring")
        return h, cache2.k, cache2.v

    return fn, (w_tp, x, cache.k, cache.v)


def _build_moe_ep_decode_double():
    """The ep2 MoE decode step with the double-buffered exchange
    (``overlap=True`` via moe_ffn_ep): two half-capacity dispatch/
    combine all_to_all pairs per MoE layer plus the replicated-hidden
    all-gather — and nothing else."""
    fn0, args = _build_moe_ep_decode()

    # moe_ffn_ep resolves FLAGS_ep_overlap at trace time: pin the flag
    # around every trace of fn so the site is independent of the
    # process-wide setting
    from ..core.flags import flag, set_flags

    prev = flag("ep_overlap")

    def fn(*a):
        set_flags({"ep_overlap": True})
        try:
            return fn0(*a)
        finally:
            set_flags({"ep_overlap": prev})

    return fn, args


SPMD_SITES: List[SpmdSite] = [
    SpmdSite("mp.column_row_linear", _build_mp_linear,
             allowed=frozenset({"all-reduce"}),
             expects_constraint=True),
    SpmdSite("ring_attention.sharded", _build_ring_attention,
             allowed=frozenset({"collective-permute"})),
    SpmdSite("moe.expert_parallel", _build_moe_ep,
             allowed=frozenset({"all-to-all", "all-reduce"})),
    # tensor-parallel serving (ISSUE 10): the TP decode/prefill
    # programs declare their per-layer psum pair; shard_map fixes the
    # output layout via out_specs (S-UNSPEC)
    SpmdSite("tp.decode", _build_tp_decode,
             allowed=frozenset({"all-reduce"}),
             expects_constraint=True),
    SpmdSite("tp.prefill_chunk", _build_tp_prefill_chunk,
             allowed=frozenset({"all-reduce"}),
             expects_constraint=True),
    # expert-parallel MoE decode (ISSUE 15): the per-layer all-to-all
    # dispatch/combine pair + the replicated-hidden all-gather
    SpmdSite("moe.ep_decode", _build_moe_ep_decode,
             allowed=frozenset({"all-to-all", "all-gather"}),
             expects_constraint=True),
    # collective overlap (ISSUE 19): the ring-reduce TP decode carries
    # collective-permutes ONLY (an all-reduce is a stray blocking
    # psum); the double-buffered EP exchange keeps the a2a/gather
    # contract with doubled pair count (checked exactly by S-OVERLAP)
    SpmdSite("overlap.tp_decode_ring", _build_tp_decode_ring,
             allowed=frozenset({"collective-permute"}),
             expects_constraint=True),
    SpmdSite("overlap.moe_ep_double", _build_moe_ep_decode_double,
             allowed=frozenset({"all-to-all", "all-gather"}),
             expects_constraint=True),
]


def run_spmd_pass(sites=None) -> List[Finding]:
    """SPMD findings over the distributed-surface inventory. Returns []
    without checking when the virtual mesh is unavailable (process
    started without the fake-device flag — e.g. attached to a real
    TPU); the tier-1 test always runs with the mesh."""
    if not mesh_available():
        return []
    findings: List[Finding] = []
    for site in (SPMD_SITES if sites is None else sites):
        findings += check_spmd_site(site)
    return waive_from_sources(findings, repo_root())
