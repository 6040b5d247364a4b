"""Jaxpr contract auditor — static verification of the backend contract.

PRs 2-5 built *models* of the solver programs (ledger byte/FLOP models,
comm models, fused stream tables, compile watch); this module checks the
*programs* against those models before anything executes. A jaxpr is a
complete, cheap-to-obtain IR: ``jax.make_jaxpr`` abstractly traces an
entry point without running it, and every property the models assert —
how many collectives an iteration issues, whether the fused vector tier
actually engaged, where precision changes — is a countable fact of that
IR. The passes:

* **collective census** — count ``psum``/``ppermute``/``all_gather``
  per iteration body (the outermost ``while`` of the traced solve) and
  assert equality with the declared comm contracts
  (``telemetry.ledger.DIST_CG_COLLECTIVES`` — the same table
  ``parallel.dist_solver`` prices its comm model from, so the model and
  the program are checked against ONE declaration). The pipelined CG's
  single stacked psum is verified down to its element count.
* **fusion engagement** — count the fused vector-algebra passes
  (``ops.fused_vec._fused_pass`` call sites in the iteration body) and
  recompute the per-iteration n-vector stream count from the jaxpr; the
  result must match ``ledger.KRYLOV_VEC_STREAMS_FUSED`` where the
  contract declares an exact value. A silently-dead fused path (env on,
  kernels not engaged) changes both counts and fails the audit.
* **dtype discipline** — flag ``convert_element_type`` on vector-sized
  values that narrows (f64→f32) or widens outside the declared
  mixed-precision seams (make_solver's precond cast, the df32 pair).
* **host sync / transfer** — flag ``pure_callback`` / debug callbacks /
  infeed-outfeed inside iteration bodies (a host round trip per
  iteration is the dispatch-overhead failure mode of VERDICT r5).
* **donation audit** — read the lowered program's input/output aliasing
  and assert it matches ``DONATION_CONTRACTS`` (all zero today: the
  groundwork check for ROADMAP item 1's resident solve loop — when
  donation lands, the contract is updated in the same commit or CI
  fails).

Vector-stream counting model (mirrors how KRYLOV_VEC_STREAMS_FUSED was
derived — the streaming floor of a perfectly fused backend):

* an engaged fused pass (``_fused_pass``, the compound kernels) moves
  exactly its vector operands: reads + writes, dots ride free;
* a standalone reduction (``dot_general``/``reduce_sum`` to a scalar)
  re-reads each distinct vector operand once;
* a maximal connected group of elementwise ops is ONE pass: its
  distinct external vector inputs are read once, its externally
  consumed vector outputs written once (XLA's elementwise fusion);
* operator applications (the SpMV kernels) and the preconditioner are
  charged by ``mv_cost``/``cycle_cost_model``, not as vector streams;
* guard-commit merges (``select_n`` / ``_where``) are register-level
  selects the floor does not charge.

Avals of size k·n count as k streams (Krylov basis matrices). ``n`` is
known to the audit (it builds the probe problem).
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "iter_eqns", "find_while_bodies", "collective_census",
    "vector_streams", "dtype_casts", "host_callbacks", "donation_audit",
    "audit_solver", "audit_dist_cg", "audit_make_solver", "audit_serve",
    "audit_setup", "check_setup", "audit_structure", "check_structure",
    "audit_entry_points",
    "run_audit", "format_report",
]

# ---------------------------------------------------------------------------
# eqn classification
# ---------------------------------------------------------------------------

#: nested-jit callee names -> role. Operator kernels and the preconditioner
#: are charged by the ledger's mv_cost/cycle models, not as vector
#: streams; select merges are free at the streaming floor.
PJIT_ROLES = {
    "_fused_pass": "fused_vec",
    "dia_spmv": "spmv", "dia_spmv_dots": "spmv", "_dia_fused": "spmv",
    "dia_residual_dot": "spmv", "dia_residual_df": "spmv",
    "dense_window_spmv": "spmv", "dense_window_fused": "spmv",
    "well_spmv": "spmv",
    "audit_precond": "precond", "apply": "precond",
    "_where": "select",
}

_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "neg", "abs", "sign", "max",
    "min", "exp", "log", "sqrt", "rsqrt", "integer_pow", "pow",
    "floor", "ceil", "round", "is_finite", "and", "or", "not", "xor",
    "eq", "ne", "lt", "le", "gt", "ge", "real", "imag", "conj",
    "convert_element_type", "broadcast_in_dim", "copy", "nextafter",
    "square", "tanh", "logistic", "erf", "clamp",
})

_REDUCE = frozenset({"reduce_sum", "reduce_max", "reduce_min",
                     "reduce_and", "reduce_or", "reduce_prod",
                     "dot_general", "argmax", "argmin"})

_COLLECTIVES = ("psum", "ppermute", "all_gather", "all_to_all",
                "pmax", "pmin", "axis_index")

_CONTROL = frozenset({"while", "scan", "cond"})

#: sub-jaxprs we deliberately do NOT descend into: Pallas kernel bodies
#: are VMEM-register programs (their internals are covered by the kernel
#: tests, and their memory behavior is what the stream model charges at
#: the call site).
_NO_DESCEND = frozenset({"pallas_call"})


def _subjaxprs(eqn) -> Iterable[Tuple[str, Any]]:
    """(param_name, jaxpr) for every jaxpr-valued param of ``eqn``."""
    if eqn.primitive.name in _NO_DESCEND:
        return
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for v in vals:
            j = getattr(v, "jaxpr", v)
            if hasattr(j, "eqns"):
                yield key, j


def iter_eqns(jaxpr, path: str = "") -> Iterable[Tuple[Any, str]]:
    """Yield (eqn, path) over ``jaxpr`` and every sub-jaxpr (while/scan/
    cond/jit/shard_map/custom_* bodies; Pallas kernels excluded)."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        for key, sub in _subjaxprs(eqn):
            yield from iter_eqns(
                sub, path + "/" + eqn.primitive.name + ":" + key)


def find_while_bodies(jaxpr) -> List[Any]:
    """Body jaxprs of every ``while`` eqn, outermost first — index 0 is
    the solver's iteration body for every Krylov loop in this repo."""
    out = []
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name == "while":
            out.append(eqn.params["body_jaxpr"].jaxpr)
    return out


def _aval(v):
    return getattr(v, "aval", None)


def _size(v) -> int:
    a = _aval(v)
    shape = getattr(a, "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape)) if shape else 1


def _vec_weight(v, n: int) -> int:
    """Stream weight of a value: k for a size-k·n aval (k >= 1), else 0.
    Scalars, flags and small state buffers are free."""
    size = _size(v)
    if n <= 0 or size < n or size % n:
        return 0
    return size // n


# ---------------------------------------------------------------------------
# collective census
# ---------------------------------------------------------------------------

def collective_census(jaxpr) -> Dict[str, Any]:
    """Counts of the collective primitives in ``jaxpr`` (recursive),
    plus the element count each psum carries (the wire payload of the
    merged-reduction contract)."""
    counts: Dict[str, int] = {}
    psum_elems: List[int] = []
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _COLLECTIVES:
            counts[name] = counts.get(name, 0) + 1
            if name == "psum":
                psum_elems.append(sum(_size(v) for v in eqn.invars))
    out: Dict[str, Any] = {k: counts.get(k, 0)
                           for k in ("psum", "ppermute", "all_gather",
                                     "all_to_all")}
    out["psum_elems"] = psum_elems
    return out


# ---------------------------------------------------------------------------
# vector-stream counting
# ---------------------------------------------------------------------------

class _Node:
    __slots__ = ("kind", "role", "prim", "vin", "vout", "win", "wout")

    def __init__(self, kind, role, prim, vin, vout, win, wout):
        self.kind = kind          # elementwise | reduce | opaque | other
        self.role = role          # for opaque: fused_vec/spmv/precond/...
        self.prim = prim
        self.vin = vin            # [value ids] vector inputs
        self.vout = vout          # [value ids] vector outputs
        self.win = win            # [weights] aligned with vin
        self.wout = wout


def _flatten(jaxpr, n: int,
             roles: Optional[Dict[str, str]] = None
             ) -> Tuple[List[_Node], set]:
    """Flatten ``jaxpr`` into stream-model nodes. Unrecognized nested
    ``jit`` calls are inlined (their eqns join the flat graph with value
    identity preserved across the call boundary); recognized kernel
    jits stay opaque with their declared role."""
    roles = dict(PJIT_ROLES, **(roles or {}))
    nodes: List[_Node] = []
    counter = [0]

    def fresh():
        counter[0] += 1
        return counter[0]

    def run(jx, sub):
        def vid(atom):
            if not hasattr(atom, "count") and not hasattr(atom, "aval"):
                return None
            if type(atom).__name__ == "Literal":
                return None
            if atom not in sub:
                sub[atom] = fresh()
            return sub[atom]

        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim == "jit":
                pname = str(eqn.params.get("name", ""))
                role = roles.get(pname)
                if role is None:
                    inner = eqn.params["jaxpr"].jaxpr
                    isub: Dict[Any, int] = {}
                    for cv in inner.constvars:
                        isub[cv] = fresh()
                    for iv, outer in zip(inner.invars, eqn.invars):
                        oid = vid(outer)
                        isub[iv] = oid if oid is not None else fresh()
                    run(inner, isub)
                    for ov, outer in zip(inner.outvars, eqn.outvars):
                        iid = isub.get(ov)
                        sub[outer] = iid if iid is not None else fresh()
                    continue
                vin = [(vid(v), _vec_weight(v, n)) for v in eqn.invars]
                vout = [(vid(v), _vec_weight(v, n)) for v in eqn.outvars]
                nodes.append(_Node(
                    "opaque", role, pname,
                    [i for i, w in vin if w], [i for i, w in vout if w],
                    [w for _, w in vin if w], [w for _, w in vout if w]))
                continue
            if prim in ("select_n",):
                # guard-commit merge: free at the streaming floor, but
                # keep value identity so clusters stay connected
                for v in eqn.outvars:
                    vid(v)
                continue
            kind = ("elementwise" if prim in _ELEMENTWISE
                    else "reduce" if prim in _REDUCE
                    else "control" if prim in _CONTROL
                    else "other")
            vin = [(vid(v), _vec_weight(v, n)) for v in eqn.invars]
            vout = [(vid(v), _vec_weight(v, n)) for v in eqn.outvars]
            nodes.append(_Node(
                kind, None, prim,
                [i for i, w in vin if w and i is not None],
                [i for i, w in vout if w and i is not None],
                [w for i, w in vin if w and i is not None],
                [w for i, w in vout if w and i is not None]))

    sub: Dict[Any, int] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        sub[v] = fresh()
    run(jaxpr, sub)
    # body outvars are externally consumed (loop carries)
    out_ids = {sub[v] for v in jaxpr.outvars if v in sub}
    return nodes, out_ids


def vector_streams(jaxpr, n: int,
                   roles: Optional[Dict[str, str]] = None
                   ) -> Dict[str, Any]:
    """Per-iteration n-vector stream count of a loop body, under the
    streaming-floor model documented in the module docstring. Returns
    the total plus its breakdown (fused passes, reductions, elementwise
    clusters, unmodeled 'other' nodes)."""
    nodes, out_ids = _flatten(jaxpr, n, roles)

    produced_by: Dict[int, _Node] = {}
    consumers: Dict[int, List[_Node]] = {}
    for node in nodes:
        for i in node.vout:
            produced_by[i] = node
        for i in node.vin:
            consumers.setdefault(i, []).append(node)

    # union-find over elementwise nodes connected by vector values
    parent: Dict[int, int] = {}

    def find(i):
        while parent.get(i, i) != i:
            parent[i] = parent.get(parent[i], parent[i])
            i = parent[i]
        return i

    def union(a, b):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb

    ew = [node for node in nodes if node.kind == "elementwise"]
    index = {id(node): k for k, node in enumerate(nodes)}
    for node in ew:
        parent.setdefault(index[id(node)], index[id(node)])
    for node in ew:
        for i in node.vin:
            prod = produced_by.get(i)
            if prod is not None and prod.kind == "elementwise":
                union(index[id(node)], index[id(prod)])

    clusters: Dict[int, List[_Node]] = {}
    for node in ew:
        clusters.setdefault(find(index[id(node)]), []).append(node)

    total = 0
    fused_passes = 0
    breakdown = {"fused": 0, "reduce": 0, "elementwise": 0, "other": 0}
    others: List[str] = []
    for node in nodes:
        if node.kind == "opaque":
            if node.role == "fused_vec":
                fused_passes += 1
                s = sum(node.win) + sum(node.wout)
                total += s
                breakdown["fused"] += s
            # spmv/precond/select: charged by the operator/cycle models
        elif node.kind == "reduce":
            s = sum(w for i, w in
                    dict(zip(node.vin, node.win)).items())
            total += s
            breakdown["reduce"] += s
        elif node.kind in ("other", "control"):
            s = sum(node.win) + sum(node.wout)
            total += s
            breakdown["other"] += s
            if s:
                others.append(node.prim)
    for members in clusters.values():
        member_set = {id(m) for m in members}
        ins: Dict[int, int] = {}
        outs: Dict[int, int] = {}
        for node in members:
            for i, w in zip(node.vin, node.win):
                prod = produced_by.get(i)
                if prod is None or id(prod) not in member_set:
                    ins[i] = w
            for i, w in zip(node.vout, node.wout):
                cons = consumers.get(i, [])
                ext = any(id(c) not in member_set for c in cons)
                if ext or i in out_ids:
                    outs[i] = w
        s = sum(ins.values()) + sum(outs.values())
        total += s
        breakdown["elementwise"] += s
    return {"streams": int(total), "fused_passes": int(fused_passes),
            "breakdown": breakdown, "unmodeled": sorted(set(others))}


# ---------------------------------------------------------------------------
# dtype discipline
# ---------------------------------------------------------------------------

def dtype_casts(jaxpr, n: int) -> List[Dict[str, Any]]:
    """Every ``convert_element_type`` on a vector-sized float value that
    changes the float width: the narrowings are the df32-path hazards,
    the widenings the literal-promotion drift."""
    out = []
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = _aval(eqn.invars[0])
        dst = _aval(eqn.outvars[0])
        if src is None or dst is None or not _vec_weight(eqn.outvars[0], n):
            continue
        try:
            sdt, ddt = np.dtype(src.dtype), np.dtype(dst.dtype)
        except TypeError:
            continue
        if sdt.kind not in "fc" or ddt.kind not in "fc":
            continue
        if sdt.itemsize == ddt.itemsize:
            continue
        out.append({
            "kind": "downcast" if ddt.itemsize < sdt.itemsize
            else "upcast",
            "from": sdt.name, "to": ddt.name,
            "elements": _size(eqn.outvars[0]), "path": path})
    return out


# ---------------------------------------------------------------------------
# host sync / transfer
# ---------------------------------------------------------------------------

_HOST_PRIMS = ("pure_callback", "debug_callback", "io_callback",
               "infeed", "outfeed", "host_callback", "debug_print")


def host_callbacks(jaxpr) -> List[Dict[str, str]]:
    """Host round trips inside the (traced) program — each one inside
    an iteration body serializes the loop on the host."""
    out = []
    for eqn, path in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if any(name.startswith(p) or p in name for p in _HOST_PRIMS):
            out.append({"primitive": name, "path": path})
    return out


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------

def donation_audit(fn, *args, **kwargs) -> Dict[str, Any]:
    """Lower ``fn`` (a jitted/watched callable) and read the program's
    input->output buffer aliasing. Donation shows up in the StableHLO as
    ``tf.aliasing_output`` arg attributes; zero means every solve call
    allocates fresh result buffers (the resident-loop gap, ROADMAP 1)."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        import jax
        fn = jax.jit(fn)
        lower = fn.lower
    lowered = lower(*args, **kwargs)
    try:
        text = lowered.as_text()
    except Exception:
        text = ""
    donated = text.count("tf.aliasing_output")
    return {"donated_args": int(donated),
            "aliasing_present": donated > 0}


# ---------------------------------------------------------------------------
# probe problems + env control
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _env(**overrides):
    """Set env knobs for the duration of a trace (every gate in ops/*
    reads its knob at trace time). ``None`` removes the variable."""
    saved = {}
    for key, val in overrides.items():
        saved[key] = os.environ.get(key)
        if val is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = str(val)
    try:
        yield
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val


@functools.lru_cache(maxsize=4)
def _probe_problem(m: int = 8):
    """Small 3-D Poisson DIA operator + rhs + Jacobi diagonal, f32 —
    large enough that every vector is unmistakably 'vector-sized'."""
    import jax.numpy as jnp
    from amgcl_tpu.ops import device as dev
    from amgcl_tpu.utils.sample_problem import poisson3d
    A, rhs = poisson3d(m)
    Ad = dev.to_device(A, "dia", jnp.float32)
    rhs32 = jnp.asarray(rhs, jnp.float32)
    dinv = jnp.asarray(1.0 / A.diagonal(), jnp.float32)
    return Ad, rhs32, dinv


def _audit_precond(dinv):
    """A named, jitted Jacobi preconditioner: shows up in the traced
    body as one opaque ``audit_precond`` jit (role 'precond'), exactly
    like the real hierarchy apply is priced — by the cycle model, not as
    Krylov vector streams."""
    import jax

    def audit_precond(r):
        return dinv * r
    return jax.jit(audit_precond)


#: trace-time env for the ENGAGED configuration: fused tier on and the
#: kernels routed through the interpret seam so the audit sees the
#: production jaxpr on any backend.
_ENGAGED_ENV = dict(AMGCL_TPU_FUSED_VEC="1", AMGCL_TPU_PALLAS="1",
                    AMGCL_TPU_PALLAS_INTERPRET="1")


def solver_registry() -> Dict[str, Any]:
    from amgcl_tpu import solver as S
    return {"CG": S.CG, "BiCGStab": S.BiCGStab, "BiCGStabL": S.BiCGStabL,
            "GMRES": S.GMRES, "FGMRES": S.FGMRES, "LGMRES": S.LGMRES,
            "IDRs": S.IDRs, "Richardson": S.Richardson,
            "PreOnly": S.PreOnly}


def audit_solver(name: str, fused: bool = True, m: int = 8,
                 solver=None, precond=None) -> Dict[str, Any]:
    """Abstractly trace one Krylov solver's ``solve`` and measure its
    iteration body: fused passes, vector streams, collectives, dtype
    casts, host callbacks. No execution — ``jax.make_jaxpr`` only.
    ``solver``/``precond`` override the probe defaults (the negative
    tests inject hazards through them; a custom precond must be a
    jitted function named ``audit_precond`` to keep the stream model's
    role classification)."""
    import jax
    Ad, rhs, dinv = _probe_problem(m)
    n = int(rhs.shape[0])
    if solver is None:
        solver = solver_registry()[name](maxiter=10)
    if precond is None:
        precond = _audit_precond(dinv)
    env = dict(_ENGAGED_ENV)
    if not fused:
        env["AMGCL_TPU_FUSED_VEC"] = "0"
    with _env(**env):
        jx = jax.make_jaxpr(
            lambda b: solver.solve(Ad, precond, b))(rhs)
    bodies = find_while_bodies(jx.jaxpr)
    rec: Dict[str, Any] = {"entry": "solver." + name, "n": n,
                           "fused_env": bool(fused),
                           "while_loops": len(bodies)}
    if not bodies:                        # PreOnly has no loop
        rec.update(streams=0, fused_passes=0,
                   collectives=collective_census(jx.jaxpr),
                   casts=dtype_casts(jx.jaxpr, n),
                   host_callbacks=host_callbacks(jx.jaxpr))
        return rec
    body = bodies[0]
    vs = vector_streams(body, n)
    rec.update(streams=vs["streams"], fused_passes=vs["fused_passes"],
               stream_breakdown=vs["breakdown"],
               unmodeled=vs["unmodeled"],
               collectives=collective_census(body),
               casts=dtype_casts(body, n),
               host_callbacks=host_callbacks(body))
    return rec


def audit_dist_cg(pipelined: bool = False, m: int = 8,
                  mesh=None) -> Dict[str, Any]:
    """Trace the distributed CG body over the available mesh and take
    the collective census of its iteration body."""
    import jax
    import jax.numpy as jnp
    from amgcl_tpu.parallel.mesh import (make_mesh, put_with_sharding,
                                         ROWS_AXIS)
    from amgcl_tpu.parallel.dist_matrix import DistDiaMatrix
    from amgcl_tpu.parallel import dist_solver as ds
    from amgcl_tpu.utils.sample_problem import poisson3d
    from jax.sharding import NamedSharding, PartitionSpec as P

    nd_avail = len(jax.devices())
    if mesh is None:
        mesh = make_mesh(nd_avail)
    nd = int(mesh.shape[ROWS_AXIS])
    entry = "parallel.dist_cg_pipelined" if pipelined \
        else "parallel.dist_cg"
    if nd < 2:
        return {"entry": entry, "skipped":
                "collective census needs >= 2 devices (have %d); run "
                "via `python -m amgcl_tpu.analysis`, which forces a "
                "virtual 8-device mesh" % nd}
    A, rhs = poisson3d(m)
    Ad = DistDiaMatrix.from_csr(A, mesh)
    build = ds._compiled_dist_cg_pipelined if pipelined \
        else ds._compiled_dist_cg
    fn = build(mesh, Ad.offsets, Ad.shape, 10, 1e-6)
    vec = NamedSharding(mesh, P(ROWS_AXIS))
    f = put_with_sharding(jnp.ones(Ad.shape[0]), vec)
    x0 = put_with_sharding(jnp.zeros(Ad.shape[0]), vec)
    di = put_with_sharding(jnp.ones(Ad.shape[0]), vec)
    jx = jax.make_jaxpr(fn._jitted)(Ad.data, f, x0, di)
    bodies = find_while_bodies(jx.jaxpr)
    rec: Dict[str, Any] = {"entry": entry, "devices": nd,
                           "halo_width": int(Ad.halo),
                           "while_loops": len(bodies)}
    body = bodies[0]
    rec["collectives"] = collective_census(body)
    rec["host_callbacks"] = host_callbacks(body)
    rec["setup_collectives"] = collective_census(jx.jaxpr)
    return rec


def audit_comm_stages(mesh=None, m: int = 8) -> List[Dict[str, Any]]:
    """Abstractly trace every comm-measurement stage pair
    (telemetry/comm.py: halo / psum / representative iteration, measured
    + comm-ablated) over the available mesh and take the collective
    census of each — checked by :func:`check_comm_stages` against
    ``ledger.COMM_STAGE_CONTRACTS``. The measured variants must issue
    exactly the declared collectives; the ablated stand-ins must issue
    NONE (a collective surviving ablation poisons the subtraction that
    attributes comm wall time). ``jax.make_jaxpr`` only, no execution."""
    import jax
    from amgcl_tpu.parallel.mesh import make_mesh, ROWS_AXIS
    if mesh is None:
        mesh = make_mesh(len(jax.devices()))
    nd = int(mesh.shape[ROWS_AXIS])
    if nd < 2:
        return [{"entry": "telemetry.comm_stages", "skipped":
                 "collective census needs >= 2 devices (have %d); run "
                 "via `python -m amgcl_tpu.analysis`, which forces a "
                 "virtual 8-device mesh" % nd}]
    from amgcl_tpu.telemetry import comm as C
    from amgcl_tpu.parallel.dist_matrix import DistDiaMatrix
    from amgcl_tpu.parallel.dist_ell import build_dist_ell
    from amgcl_tpu.utils.sample_problem import poisson3d
    A, _ = poisson3d(m)
    ops = [DistDiaMatrix.from_csr(A, mesh), build_dist_ell(A, mesh)]
    recs: List[Dict[str, Any]] = []
    seen = set()
    for op in ops:
        for pipelined in (False, True):
            for st in C.comm_stages(op, mesh, pipelined=pipelined):
                for ablated in (False, True):
                    key = (st["contract"], ablated)
                    if key in seen:
                        continue        # halo/psum repeat across bodies
                    seen.add(key)
                    fn = st["fn_ablated"] if ablated else st["fn"]
                    jx = jax.make_jaxpr(getattr(fn, "_jitted", fn))(
                        *st["args"])
                    recs.append({
                        "entry": getattr(
                            fn, "_watched_name",
                            "telemetry.comm_%s%s"
                            % (st["key"],
                               "_ablated" if ablated else "")),
                        "stage": st["contract"], "ablated": ablated,
                        "devices": nd,
                        "collectives": collective_census(jx.jaxpr)})
    return recs


def check_comm_stages(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Findings for one audit_comm_stages record: measured stages must
    match ``ledger.COMM_STAGE_CONTRACTS`` collective for collective;
    ablated stand-ins must census to exactly 0."""
    from amgcl_tpu.telemetry.ledger import COMM_STAGE_CONTRACTS
    out: List[Dict[str, Any]] = []
    if rec.get("skipped"):
        out.append({"severity": "info", "pass": "collectives",
                    "entry": rec["entry"], "message": rec["skipped"]})
        return out
    kinds = ("psum", "ppermute", "all_gather", "all_to_all")
    got = {k: rec["collectives"].get(k, 0) for k in kinds}
    if rec["ablated"]:
        total = sum(got.values())
        if total != 0:
            out.append({
                "severity": "error", "pass": "collectives",
                "entry": rec["entry"],
                "message": "comm-ablated stand-in issues %d "
                "collective(s) (%s) — the ablation contract is a "
                "census of EXACTLY 0; any surviving collective "
                "poisons the measured-comm subtraction"
                % (total, {k: v for k, v in got.items() if v})})
        return out
    contract = COMM_STAGE_CONTRACTS.get(rec["stage"])
    if contract is None:
        return out
    want = {k: contract.get(k, 0) for k in kinds}
    if got != want:
        out.append({
            "severity": "error", "pass": "collectives",
            "entry": rec["entry"],
            "message": "measured comm stage %r census %s, contract "
            "says %s (ledger.COMM_STAGE_CONTRACTS) — the stage no "
            "longer measures what the model prices"
            % (rec["stage"], {k: v for k, v in got.items() if v},
               {k: v for k, v in want.items() if v})})
    return out


def audit_make_solver(mixed: bool = False, m: int = 8) -> Dict[str, Any]:
    """Trace ``make_solver._solve_fn`` (the fused P+S program) and audit
    dtype discipline across the whole program: with ``mixed`` the
    preconditioner runs one float width below the Krylov loop and the
    declared seam is exactly one downcast + one upcast per apply."""
    import jax
    import jax.numpy as jnp
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.solver.cg import CG
    from amgcl_tpu.utils.sample_problem import poisson3d

    A, rhs = poisson3d(m)
    n = len(rhs)
    if mixed:
        x64 = jax.config.jax_enable_x64
        if not x64:
            return {"entry": "make_solver._solve_fn", "mixed": True,
                    "skipped": "mixed-precision audit needs x64"}
        ms = make_solver(A, AMGParams(dtype=jnp.float32,
                                      coarse_enough=50),
                         solver=CG(maxiter=10),
                         solver_dtype=jnp.float64)
    else:
        ms = make_solver(A, AMGParams(dtype=jnp.float32,
                                      coarse_enough=50),
                         solver=CG(maxiter=10))
    rhs_dev = jnp.asarray(rhs, ms.solver_dtype)
    x0 = jnp.zeros_like(rhs_dev)
    with _env(**_ENGAGED_ENV):
        jx = jax.make_jaxpr(ms._solve_fn)(
            ms.A_dev, ms.A_dev64, ms.precond.hierarchy, rhs_dev, x0)
        # donation must be read off the PRODUCTION wrap (the same
        # watched_jit call __call__ runs), not a fresh jax.jit — donate
        # args configured there would be invisible to a re-wrap
        don = donation_audit(
            ms._wrapped_solve_fn(),
            ms.A_dev, ms.A_dev64, ms.precond.hierarchy, rhs_dev, x0)
    bodies = find_while_bodies(jx.jaxpr)
    body = bodies[0] if bodies else jx.jaxpr
    casts = dtype_casts(body, n)
    return {"entry": "make_solver._solve_fn", "mixed": bool(mixed),
            "n": n, "while_loops": len(bodies),
            "casts_per_iteration": casts,
            "downcasts": sum(1 for c in casts if c["kind"] == "downcast"),
            "upcasts": sum(1 for c in casts if c["kind"] == "upcast"),
            "host_callbacks": host_callbacks(body),
            "donation": don}


def audit_serve(m: int = 8, batch: int = 2) -> Dict[str, Any]:
    """Lower the resident serve loop's ACTUAL jit wrap
    (serve/service.py: ``SolverService._entry``, iterate buffer donated
    via ``donate_argnums``) over a stacked (n, B) probe and read the
    input→output buffer aliasing out of the lowered program — the
    static proof that the resident loop reuses its workspace instead of
    allocating per batch (ROADMAP item 1's donation contract)."""
    import jax.numpy as jnp
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.serve.service import SolverService
    from amgcl_tpu.solver.cg import CG
    from amgcl_tpu.utils.sample_problem import poisson3d

    A, rhs = poisson3d(m)
    ms = make_solver(A, AMGParams(dtype=jnp.float32, coarse_enough=50),
                     solver=CG(maxiter=10))
    svc = SolverService(ms, batch=batch)
    rhs2 = jnp.tile(jnp.asarray(rhs, jnp.float32)[:, None], (1, batch))
    x0 = jnp.zeros_like(rhs2)
    don = donation_audit(svc._entry, ms.A_dev, ms.A_dev64,
                         ms.precond.hierarchy, rhs2, x0)
    return {"entry": "serve.solve_step", "n": len(rhs),
            "batch": int(batch), "donation": don}


def audit_setup(m: int = 6) -> List[Dict[str, Any]]:
    """Abstractly trace every device-setup entry point (the traced
    per-level hierarchy build: MIS rounds, segment-Galerkin, smoothing
    SpGEMM, stencil pair-Galerkin) and record host callbacks,
    collectives and float-width casts — checked by :func:`check_setup`
    against ``ledger.SETUP_CONTRACTS``. ``jax.make_jaxpr`` only, no
    execution."""
    import jax
    import jax.numpy as jnp
    from amgcl_tpu.coarsening import device_mis
    from amgcl_tpu.ops import segment_spgemm as seg

    recs: List[Dict[str, Any]] = []

    def record(entry, jx, n):
        recs.append({
            "entry": entry, "n": n,
            "collectives": collective_census(jx.jaxpr),
            "casts": [c for c in dtype_casts(jx.jaxpr, 1)
                      if c["elements"] >= n],
            "host_callbacks": host_callbacks(jx.jaxpr)})

    # MIS rounds: (n, K) ELL strength adjacency, static round count
    npad = 64
    cols = jnp.zeros((npad, 8), jnp.int32)
    valid = jnp.zeros((npad, 8), bool)
    prio = jnp.arange(1, npad + 1, dtype=jnp.int32)
    jx = jax.make_jaxpr(
        lambda c, v, p: device_mis.device_aggregates(c, v, p, rounds=4))(
        cols, valid, prio)
    record("coarsening.device_aggregates", jx, npad)

    nnz, nnz_c = 48, 16
    vals = jnp.ones(nnz, jnp.float32)
    take = jnp.arange(nnz, dtype=jnp.int32)
    sidx = jnp.zeros(nnz, jnp.int32)
    jx = jax.make_jaxpr(
        lambda v, t, s: seg._galerkin_kernel(
            v, t, s, jnp.float32(1.0), nnz_c))(vals, take, sidx)
    record("ops.segment_galerkin", jx, nnz)

    jx = jax.make_jaxpr(
        lambda a, b, ia, ib, s: seg._spgemm_kernel(a, b, ia, ib, s,
                                                   nnz_c))(
        vals, vals, take, take, sidx)
    record("ops.segment_spgemm", jx, nnz)

    jx = jax.make_jaxpr(
        lambda a, d, t, s: seg._smooth_kernel(
            a, d, t, s, jnp.float32(0.5), 8, nnz_c))(
        vals, vals, take, jnp.zeros(8 + nnz, jnp.int32))
    record("ops.transfer_smooth", jx, nnz)

    # stencil pair-Galerkin: a real small grid plan's generated device fn
    from amgcl_tpu.ops.stencil import StencilGalerkinPlan, \
        host_dia_from_csr
    from amgcl_tpu.utils.sample_problem import poisson3d
    A, _ = poisson3d(m)
    Ad = host_dia_from_csr(A, (m, m, m), np.float32)
    plan = StencilGalerkinPlan(
        Ad.offsets3, Ad.offsets3, Ad.dims, (2, 2, 2),
        tuple(-(-d // 2) for d in (m, m, m)), np.float32)
    fn = plan._build_device_fn()
    a_dev = jnp.asarray(Ad.data)
    jx = jax.make_jaxpr(fn._jitted if hasattr(fn, "_jitted") else fn)(
        a_dev, a_dev)
    record("ops.stencil_galerkin", jx, int(Ad.nrows))
    return recs


def check_setup(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Findings for one audit_setup record against
    ``ledger.SETUP_CONTRACTS``: the traced per-level build must stay
    free of host callbacks and collectives, and must not change float
    width on matrix-sized values (the dtype seam is the host boundary,
    not the kernels)."""
    from amgcl_tpu.telemetry.ledger import SETUP_CONTRACTS
    contract = SETUP_CONTRACTS.get(rec["entry"])
    out: List[Dict[str, Any]] = []
    if contract is None:
        return out
    if len(rec["host_callbacks"]) != contract["host_callbacks"]:
        out.append({
            "severity": "error", "pass": "host-sync",
            "entry": rec["entry"],
            "message": "host callback %r inside the traced setup "
            "program — the per-level build must run device-side "
            "without host round trips"
            % rec["host_callbacks"][0]["primitive"]})
    cen = rec["collectives"]
    n_coll = sum(cen.get(k, 0) for k in ("psum", "ppermute",
                                         "all_gather", "all_to_all"))
    if n_coll != contract["collectives"]:
        out.append({
            "severity": "error", "pass": "collectives",
            "entry": rec["entry"],
            "message": "%d collective(s) in the serial setup program, "
            "contract says %d (the sharded MIS path has its own "
            "contract)" % (n_coll, contract["collectives"])})
    narrowing = [c for c in rec["casts"] if c["kind"] == "downcast"]
    if len(narrowing) != contract["narrowing_casts"]:
        out.append({
            "severity": "error", "pass": "dtype",
            "entry": rec["entry"],
            "message": "%d narrowing float cast(s) on matrix-sized "
            "values inside the setup kernel (contract: %d) — numeric "
            "rebuilds must stay bit-stable in the build dtype"
            % (len(narrowing), contract["narrowing_casts"])})
    return out


def audit_structure(m: int = 6) -> Dict[str, Any]:
    """Audit the operator X-ray's host-purity contract
    (``ledger.STRUCTURE_CONTRACTS``), two halves:

    * **static** — AST-scan ``telemetry/structure.py`` for imports of
      ``jax`` or of jax-importing ``amgcl_tpu.ops`` modules
      (``ops.csr`` is numpy-only and allowed): any hit means the
      "host-side analytics only" claim is structurally false.
    * **dynamic** — build a small hierarchy, snapshot the
      compile-watch totals, run a FULL ``structure_report`` (advisor
      included, every level) plus ``structure_findings``, and record
      the trace/compile delta: the X-ray must compile nothing beyond
      the entry points the build already created.
    """
    import ast
    import os as _os

    path = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "telemetry", "structure.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    jax_imports = []
    for node in ast.walk(tree):
        names = []
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            names = [node.module]
        for name in names:
            root = name.split(".")[0]
            if root == "jax" or (
                    name.startswith("amgcl_tpu.ops")
                    and not name.startswith("amgcl_tpu.ops.csr")):
                jax_imports.append(name)

    rec: Dict[str, Any] = {"entry": "telemetry.structure",
                           "jax_imports": len(jax_imports),
                           "jax_import_names": jax_imports}
    try:
        from amgcl_tpu.utils.sample_problem import poisson3d
        from amgcl_tpu.models.amg import AMG, AMGParams
        from amgcl_tpu.telemetry import compile_watch as cw
        from amgcl_tpu.telemetry.structure import structure_findings
        A, _ = poisson3d(m)
        amg = AMG(A, AMGParams(coarse_enough=20))
        before = cw.snapshot()["totals"]
        xray = amg.structure_report(advise=True)
        structure_findings(xray)
        after = cw.snapshot()["totals"]
        rec["new_traces"] = after["traces"] - before["traces"]
        rec["new_backend_compiles"] = (after["backend_compiles"]
                                       - before["backend_compiles"])
        rec["n_levels"] = len(xray.get("levels", []))
    except Exception as e:
        rec["skipped"] = "dynamic half failed: %r" % (e,)
    return rec


def check_structure(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Findings for the audit_structure record against
    ``ledger.STRUCTURE_CONTRACTS`` — the X-ray path must stay
    host-side (no jax imports) and compile-free (compile_watch delta
    0)."""
    from amgcl_tpu.telemetry.ledger import STRUCTURE_CONTRACTS
    contract = STRUCTURE_CONTRACTS.get(rec["entry"])
    out: List[Dict[str, Any]] = []
    if contract is None:
        return out
    if rec["jax_imports"] != contract["jax_imports"]:
        out.append({
            "severity": "error", "pass": "host-sync",
            "entry": rec["entry"],
            "message": "telemetry/structure.py imports %s — the "
            "operator X-ray is host-side analytics only (the module "
            "may use numpy/scipy and ops.csr, never jax or a "
            "jax-importing ops module)"
            % ", ".join(rec.get("jax_import_names", []))})
    if rec.get("skipped"):
        out.append({"severity": "info", "pass": "host-sync",
                    "entry": rec["entry"], "message": rec["skipped"]})
        return out
    for key in ("new_traces", "new_backend_compiles"):
        if rec.get(key, 0) != contract[key]:
            out.append({
                "severity": "error", "pass": "host-sync",
                "entry": rec["entry"],
                "message": "structure_report(advise=True) moved the "
                "process %s counter by %d (contract: %d) — the X-ray "
                "path compiled device work; it must stay predict-only"
                % (key, rec.get(key, 0), contract[key])})
    return out


def check_serve(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Donation contract of the resident loop: the lowered program must
    alias exactly ``DONATION_CONTRACTS['serve.solve_step']`` argument
    buffers (1 — the donated iterate). Zero means every batch allocates
    fresh result storage; more means an undeclared donation landed."""
    from amgcl_tpu.telemetry.ledger import DONATION_CONTRACTS
    out = []
    if rec.get("skipped"):
        out.append({"severity": "info", "pass": "donation",
                    "entry": rec["entry"], "message": rec["skipped"]})
        return out
    want = DONATION_CONTRACTS.get(rec["entry"], 0)
    got = rec["donation"]["donated_args"]
    if got != want:
        out.append({
            "severity": "error", "pass": "donation",
            "entry": rec["entry"],
            "message": "resident serve loop aliases %d arg buffer(s), "
            "contract declares %d — the donated iterate buffer was "
            "lost (or a new donation is undeclared); update "
            "ledger.DONATION_CONTRACTS in the same commit" % (got, want)})
    return out


# ---------------------------------------------------------------------------
# contract checks
# ---------------------------------------------------------------------------

def check_solver(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Findings for one audit_solver record against the declared
    contracts (ledger.KRYLOV_FUSED_PASSES / KRYLOV_VEC_STREAMS_FUSED)."""
    from amgcl_tpu.telemetry.ledger import (KRYLOV_FUSED_PASSES,
                                            KRYLOV_VEC_STREAMS_FUSED)
    name = rec["entry"].split(".", 1)[1]
    out = []
    contract = KRYLOV_FUSED_PASSES.get(name)
    if rec.get("skipped") or contract is None:
        return out
    if rec["fused_env"]:
        want_passes, exact_streams = contract
        if rec["fused_passes"] != want_passes:
            out.append({
                "severity": "error", "pass": "fusion",
                "entry": rec["entry"],
                "message": "fused vector tier not engaged as declared: "
                "%d _fused_pass call(s) per iteration, contract says %d "
                "(AMGCL_TPU_FUSED_VEC on; a dead fused path shows up "
                "exactly like this)" % (rec["fused_passes"],
                                        want_passes)})
        if exact_streams and rec["streams"] != \
                KRYLOV_VEC_STREAMS_FUSED.get(name):
            out.append({
                "severity": "error", "pass": "fusion",
                "entry": rec["entry"],
                "message": "per-iteration vector streams = %d but the "
                "ledger's fused model charges %d "
                "(KRYLOV_VEC_STREAMS_FUSED['%s']) — either the body or "
                "the byte model drifted" % (
                    rec["streams"],
                    KRYLOV_VEC_STREAMS_FUSED.get(name), name)})
    else:
        if rec["fused_passes"] != 0:
            out.append({
                "severity": "error", "pass": "fusion",
                "entry": rec["entry"],
                "message": "AMGCL_TPU_FUSED_VEC=0 but %d fused pass(es) "
                "still trace in" % rec["fused_passes"]})
    out += _common_body_checks(rec)
    return out


def _common_body_checks(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    out = []
    for cb in rec.get("host_callbacks", []):
        out.append({
            "severity": "error", "pass": "host-sync",
            "entry": rec["entry"],
            "message": "host callback %r inside the iteration body "
            "(path %s): one host round trip per iteration"
            % (cb["primitive"], cb["path"] or "/")})
    for c in rec.get("casts", []):
        out.append({
            "severity": "error" if c["kind"] == "downcast" else "warning",
            "pass": "dtype", "entry": rec["entry"],
            "message": "%s %s->%s on a %d-element value inside the "
            "iteration body (no declared seam here)"
            % (c["kind"], c["from"], c["to"], c["elements"])})
    return out


def check_dist(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Collective census vs the declared comm contract — the same table
    dist_solver prices its SolveReport comm model from."""
    from amgcl_tpu.telemetry.ledger import DIST_CG_COLLECTIVES
    out = []
    if rec.get("skipped"):
        out.append({"severity": "info", "pass": "collectives",
                    "entry": rec["entry"], "message": rec["skipped"]})
        return out
    key = rec["entry"].rsplit(".", 1)[1]
    contract = DIST_CG_COLLECTIVES[key]
    census = rec["collectives"]
    if census["psum"] != contract["psums"]:
        out.append({
            "severity": "error", "pass": "collectives",
            "entry": rec["entry"],
            "message": "%d psum(s) per iteration, contract says %d — "
            "a collective crept into (or fell out of) the body; the "
            "SolveReport comm model prices dots=%d" % (
                census["psum"], contract["psums"], contract["psums"])})
    if contract.get("elems_per_psum") and census["psum_elems"] and \
            max(census["psum_elems"]) != contract["elems_per_psum"]:
        out.append({
            "severity": "error", "pass": "collectives",
            "entry": rec["entry"],
            "message": "stacked psum carries %r elements, contract says "
            "%d" % (census["psum_elems"], contract["elems_per_psum"])})
    want_pp = contract["spmvs"] * (2 if rec.get("halo_width", 0) > 0
                                   and rec.get("devices", 1) > 1 else 0)
    if census["ppermute"] != want_pp:
        out.append({
            "severity": "error", "pass": "collectives",
            "entry": rec["entry"],
            "message": "%d ppermute(s) per iteration, halo contract "
            "says %d (%d SpMV(s) x fwd+bwd ring exchange)"
            % (census["ppermute"], want_pp, contract["spmvs"])})
    for cb in rec.get("host_callbacks", []):
        out.append({
            "severity": "error", "pass": "host-sync",
            "entry": rec["entry"],
            "message": "host callback %r inside the distributed "
            "iteration body" % cb["primitive"]})
    return out


def check_make_solver(rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    from amgcl_tpu.telemetry.ledger import DONATION_CONTRACTS
    out = []
    if rec.get("skipped"):
        out.append({"severity": "info", "pass": "dtype",
                    "entry": rec["entry"], "message": rec["skipped"]})
        return out
    allowed_down = 1 if rec["mixed"] else 0
    allowed_up = 1 if rec["mixed"] else 0
    if rec["downcasts"] != allowed_down or rec["upcasts"] != allowed_up:
        out.append({
            "severity": "error", "pass": "dtype",
            "entry": rec["entry"],
            "message": "iteration body has %d downcast(s)/%d upcast(s) "
            "of vector values; the declared mixed-precision seam allows "
            "exactly %d/%d (precond apply: r down, z up)"
            % (rec["downcasts"], rec["upcasts"], allowed_down,
               allowed_up)})
    for cb in rec.get("host_callbacks", []):
        out.append({
            "severity": "error", "pass": "host-sync",
            "entry": rec["entry"],
            "message": "host callback %r inside _solve_fn's iteration "
            "body" % cb["primitive"]})
    want = DONATION_CONTRACTS.get(rec["entry"], 0)
    got = rec["donation"]["donated_args"]
    if got != want:
        out.append({
            "severity": "error", "pass": "donation",
            "entry": rec["entry"],
            "message": "lowered program aliases %d arg buffer(s), "
            "contract declares %d — update "
            "ledger.DONATION_CONTRACTS with the resident-loop change "
            "that did this" % (got, want)})
    elif want == 0:
        out.append({
            "severity": "info", "pass": "donation",
            "entry": rec["entry"],
            "message": "no donated buffers: every solve allocates fresh "
            "x/r storage (ROADMAP item 1's resident loop will flip this "
            "contract)"})
    return out


def check_entry_points() -> List[Dict[str, Any]]:
    """Drift check: the watched_jit registrations the linter discovers
    in the source must be exactly compile_watch.DECLARED_ENTRY_POINTS
    (the once-upon-a-time docstring list, now code)."""
    from amgcl_tpu.analysis import lint
    from amgcl_tpu.telemetry import compile_watch as cw
    found = set(lint.watched_entry_points())
    declared = set(cw.DECLARED_ENTRY_POINTS)
    out = []
    for name in sorted(found - declared):
        out.append({
            "severity": "error", "pass": "entry-points", "entry": name,
            "message": "watched_jit(name=%r) exists in source but is "
            "not in compile_watch.DECLARED_ENTRY_POINTS" % name})
    for name in sorted(declared - found):
        out.append({
            "severity": "error", "pass": "entry-points", "entry": name,
            "message": "compile_watch.DECLARED_ENTRY_POINTS lists %r "
            "but no watched_jit registration with that name exists"
            % name})
    return out


def audit_entry_points() -> Dict[str, Any]:
    from amgcl_tpu.analysis import lint
    from amgcl_tpu.telemetry import compile_watch as cw
    return {"entry": "compile_watch.DECLARED_ENTRY_POINTS",
            "found": sorted(lint.watched_entry_points()),
            "declared": sorted(cw.DECLARED_ENTRY_POINTS)}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_audit(solvers: Optional[Sequence[str]] = None,
              dist: bool = True) -> Dict[str, Any]:
    """Run every auditor pass; returns {"records": [...], "findings":
    [...], "ok": bool} with ok = no error-severity findings. Infos
    (donation groundwork, skipped passes) never fail the audit."""
    records: List[Dict[str, Any]] = []
    findings: List[Dict[str, Any]] = []
    names = list(solvers) if solvers else sorted(solver_registry())
    for name in names:
        for fused in (True, False):
            rec = audit_solver(name, fused=fused)
            records.append(rec)
            findings += check_solver(rec)
    if dist:
        for pipelined in (False, True):
            rec = audit_dist_cg(pipelined=pipelined)
            records.append(rec)
            findings += check_dist(rec)
        for rec in audit_comm_stages():
            records.append(rec)
            findings += check_comm_stages(rec)
    for mixed in (False, True):
        rec = audit_make_solver(mixed=mixed)
        records.append(rec)
        findings += check_make_solver(rec)
    rec = audit_serve()
    records.append(rec)
    findings += check_serve(rec)
    for rec in audit_setup():
        records.append(rec)
        findings += check_setup(rec)
    rec = audit_structure()
    records.append(rec)
    findings += check_structure(rec)
    findings += check_entry_points()
    errors = [f for f in findings if f["severity"] == "error"]
    return {"records": records, "findings": findings,
            "errors": len(errors), "ok": not errors}


def format_report(result: Dict[str, Any]) -> str:
    lines = ["Jaxpr audit: %d record(s), %d finding(s), %s" % (
        len(result["records"]), len(result["findings"]),
        "OK" if result["ok"] else "FAIL")]
    for rec in result["records"]:
        if rec.get("skipped"):
            lines.append("  %-34s SKIPPED (%s)" % (rec["entry"],
                                                   rec["skipped"]))
            continue
        bits = []
        if "streams" in rec:
            bits.append("streams=%d fused_passes=%d (tier %s)"
                        % (rec["streams"], rec["fused_passes"],
                           "on" if rec.get("fused_env") else "off"))
        cen = rec.get("collectives")
        if cen and (cen["psum"] or cen["ppermute"]):
            bits.append("psum=%d%s ppermute=%d" % (
                cen["psum"],
                "x%d" % max(cen["psum_elems"])
                if cen.get("psum_elems") else "",
                cen["ppermute"]))
        if "downcasts" in rec:
            bits.append("casts %dv/%d^ donated=%d" % (
                rec["downcasts"], rec["upcasts"],
                rec["donation"]["donated_args"]))
        elif "donation" in rec:
            bits.append("batch=%s donated=%d" % (
                rec.get("batch", "-"),
                rec["donation"]["donated_args"]))
        lines.append("  %-34s %s" % (rec["entry"], "  ".join(bits)))
    for f in result["findings"]:
        lines.append("  [%s/%s] %s: %s" % (f["severity"], f["pass"],
                                           f["entry"], f["message"]))
    return "\n".join(lines)
