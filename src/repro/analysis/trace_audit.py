"""Lowering-time invariant audit (layer 1 of the static analyzer).

Lowers the repo's hot entry points against abstract
``ShapeDtypeStruct``s — no data, no kernels executed — and walks the
resulting jaxprs (and, for the collective check, compiled HLO) to verify
contracts that unit tests cannot pin down at the Python level:

T001  dtype contracts: packed state words are uint32 end to end, node /
      segment ids are int32, BFS planes are int8.  A silent upcast
      (e.g. uint32 -> int64 from a stray Python int) doubles the packed
      representation and breaks the word-RAM cost model.
T002  no host round-trips inside step functions: any callback /
      device_put / infeed primitive in a superstep jaxpr means a
      host-device sync per superstep.
T003  pow2 padding: the dense engine's heterogeneous bucket widths must
      be minimal powers of two (min 4) so mixed-size automata share
      compiled shapes.
T004  retrace budget: a canonical mixed workload on both engines must
      stay within a fixed number of distinct jit signatures, and a
      repeat of the same workload must add ZERO new signatures.
T005  collective traffic: the sharded batched superstep's all-gather
      bytes (parsed from compiled HLO via ``launch.hlo_analysis``) must
      not exceed the planner's wire model R*Vp*S*(n-1)/n beyond
      tolerance.  Needs >= 2 devices; reported as a skip-note otherwise.
T006  lowering failure: an entry point that no longer lowers at all.

``audit_jaxpr`` is the reusable primitive — tests hand it deliberately
bad step functions to prove the walker catches them.

Each named check's result is cached on disk under
``<root>/.cache/repro-analysis/``, keyed by the content hash of the
source files the check lowers plus the jax version and device
signature — unchanged entry points skip re-lowering entirely, and the
driver reports hit/miss counts in its notes (they land in the CI
findings artifact).  ``--no-trace-cache`` (or ``use_cache=False``)
forces a live run.
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import asdict
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .findings import Finding

# Primitive-name markers that mean "host round-trip inside the step".
FORBIDDEN_PRIM_MARKERS = ("callback", "device_put", "infeed", "outfeed")

# Wire-model tolerance for T005: XLA may pad/fuse the gather, and the
# regex wire model is deliberately simple, so allow headroom before
# calling it a regression.
COLLECTIVE_TOLERANCE = 1.75
COLLECTIVE_SLACK_BYTES = 4096

# Distinct-signature budgets for the canonical workload (T004).  These
# are measured-tight (see tests/test_analysis.py): the workload below
# produces exactly 2 dense signatures and 0 ring signatures today (the
# metro graph sits below the ring kernel threshold, so its wavefront
# runs the scalar path with no jit dispatch at all).  The budget leaves
# headroom so a benign new bucket does not fail CI, while a per-query
# retrace blowup (the bug class this guards against — signatures
# scaling with the number of queries) still does.
RETRACE_BUDGET = {"dense": 3, "ring": 2}

CANONICAL_QUERIES = (
    "l5/l1",
    ("l5/(l1)*", 0, None),
    ("(l1|l2)/^bus", None, 3),
    "l5/l1",          # replay: must hit the same compiled signature
)


def _walk_jaxprs(jaxpr) -> List:
    """The jaxpr plus every sub-jaxpr reachable through eqn params."""
    out, stack, seen = [], [jaxpr], set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        out.append(j)
        for eqn in j.eqns:
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else (val,)
                for v in vals:
                    inner = getattr(v, "jaxpr", None)
                    if inner is not None:
                        stack.append(inner)
                    elif hasattr(v, "eqns"):
                        stack.append(v)
    return out


def audit_jaxpr(
    fn: Callable,
    args: Sequence,
    *,
    label: str,
    file: str,
    line: int = 0,
    expect_out_dtypes: Optional[Sequence] = None,
    forbid_prims: bool = True,
) -> List[Finding]:
    """Lower ``fn`` against abstract ``args`` and audit the jaxpr.

    ``expect_out_dtypes``: required dtype per flattened output (None
    entries skip).  ``forbid_prims``: fail on any host-round-trip
    primitive (see :data:`FORBIDDEN_PRIM_MARKERS`).
    """
    findings: List[Finding] = []
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as exc:  # noqa: BLE001 - any lowering failure is T006
        findings.append(Finding(
            file, line, "T006",
            f"{label}: entry point no longer lowers: {type(exc).__name__}: "
            f"{exc}",
            "fix the traced signature or shapes; run the audit locally to "
            "reproduce", f"{label}:lowering-failure"))
        return findings

    if expect_out_dtypes is not None:
        outs = closed.jaxpr.outvars
        for i, want in enumerate(expect_out_dtypes):
            if want is None or i >= len(outs):
                continue
            got = outs[i].aval.dtype
            if got != np.dtype(want):
                findings.append(Finding(
                    file, line, "T001",
                    f"{label}: output {i} is {got}, contract requires "
                    f"{np.dtype(want)}",
                    "check for a silent upcast (Python int arithmetic, "
                    "np default dtypes) in the step math",
                    f"{label}:out{i}:{got}"))

    if forbid_prims:
        for j in _walk_jaxprs(closed.jaxpr):
            for eqn in j.eqns:
                pname = eqn.primitive.name
                if any(m in pname for m in FORBIDDEN_PRIM_MARKERS):
                    findings.append(Finding(
                        file, line, "T002",
                        f"{label}: forbidden primitive '{pname}' in the "
                        "step jaxpr — host round-trip per superstep",
                        "keep step functions pure device code; do host "
                        "work between supersteps",
                        f"{label}:prim:{pname}"))
    return findings


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------
# T001/T002: kernel + superstep entry-point contracts
# ---------------------------------------------------------------------

def check_kernel_contracts() -> List[Finding]:
    # NB: go through the submodule path — `from ..kernels import nfa_step`
    # would resolve to the re-exported *function* (see kernels/__init__).
    from ..kernels.nfa_step import nfa_step_pallas
    from ..kernels import ops

    u32, i32 = jnp.uint32, jnp.int32
    findings: List[Finding] = []
    findings += audit_jaxpr(
        lambda X, bwd: nfa_step_pallas(X, bwd, interpret=True),
        (_sds((512, 2), u32), _sds((33, 2), u32)),
        label="kernels.nfa_step_pallas", file="src/repro/kernels/nfa_step.py",
        expect_out_dtypes=[u32])
    findings += audit_jaxpr(
        ops.nfa_step, (_sds((700, 1), u32), _sds((7, 1), u32)),
        label="kernels.ops.nfa_step", file="src/repro/kernels/ops.py",
        expect_out_dtypes=[u32])
    nw = 64  # 4 superblocks of SB_WORDS=16
    findings += audit_jaxpr(
        ops.superblock_popcounts, (_sds((nw,), u32),),
        label="kernels.ops.superblock_popcounts",
        file="src/repro/kernels/rank_popcount.py",
        expect_out_dtypes=[i32])
    findings += audit_jaxpr(
        ops.rank1,
        (_sds((nw,), u32), _sds((nw // 16 + 1,), i32), _sds((128,), i32)),
        label="kernels.ops.rank1", file="src/repro/kernels/ops.py",
        expect_out_dtypes=[i32])
    findings += audit_jaxpr(
        lambda v, s: ops.segment_or(v, s, 64),
        (_sds((256, 2), u32), _sds((256,), i32)),
        label="kernels.ops.segment_or", file="src/repro/kernels/ops.py",
        expect_out_dtypes=[u32])
    return findings


def check_hetero_bfs() -> List[Finding]:
    """The hetero-bucket vmapped BFS over subject-sorted edges with their
    segment offsets: int32 edge ids, int8 planes in and out, no host
    round-trips across the whole unrolled superstep chain."""
    from ..core import dense

    i8, i32 = jnp.int8, jnp.int32
    R, V, S, L, E = 3, 16, 8, 4, 40
    return audit_jaxpr(
        lambda *a: dense._bfs_hetero(*a[:6], num_nodes=V,
                                     max_steps=V * S + 1, off=a[6],
                                     n_sorted=E),
        (_sds((E,), i32), _sds((E,), i32), _sds((E,), i32),
         _sds((R, L + 1, S), i8), _sds((R, S, S), i8),
         _sds((R, V, S), i8), _sds((V + 1,), i32)),
        label="dense._bfs_hetero", file="src/repro/core/dense.py",
        expect_out_dtypes=[i8])


def check_sharded_steps() -> List[Finding]:
    """Sharded superstep builders on a mesh over the local devices (a
    1-device mesh still exercises lowering, dtypes, and the primitive
    walk; the collective-bytes check separately needs >= 2)."""
    from jax.sharding import Mesh

    from ..core import distributed as dist

    findings: List[Finding] = []
    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("data",))
    n = len(devs)
    i8, i32, u32 = jnp.int8, jnp.int32, jnp.uint32

    R, Vp, S, L, Emax = 4, 32 * n, 8, 3, 64 * n
    step = dist.make_superstep_batched(mesh, ("data",))
    with mesh:
        findings += audit_jaxpr(
            step,
            (_sds((R, Vp, S), i8), _sds((R, Vp, S), i8),
             _sds((n, Emax // n), i32), _sds((n, Emax // n), i32),
             _sds((n, Emax // n), i32),
             _sds((R, L + 1, S), i8), _sds((R, S, S), i8)),
            label="distributed.make_superstep_batched",
            file="src/repro/core/distributed.py",
            expect_out_dtypes=[i8, i8])

    task_step = dist.make_task_shard_step(mesh, ("data",))
    with mesh:
        findings += audit_jaxpr(
            task_step, (_sds((16 * n, 2), u32), _sds((33, 2), u32)),
            label="distributed.make_task_shard_step",
            file="src/repro/core/distributed.py",
            expect_out_dtypes=[u32])
    return findings


# ---------------------------------------------------------------------
# T003: pow2 bucket padding
# ---------------------------------------------------------------------

def check_pow2_padding() -> List[Finding]:
    from ..core.dense import DenseRPQ

    findings: List[Finding] = []
    for S in range(1, 129):
        w = DenseRPQ._pad_width(S)
        minimal = max(4, 1 << (S - 1).bit_length())
        if w != minimal:
            findings.append(Finding(
                "src/repro/core/dense.py", 0, "T003",
                f"_pad_width({S}) = {w}; hetero buckets must pad to the "
                f"minimal power of two >= max(S, 4) (= {minimal}) to share "
                "compiled shapes without waste",
                "restore next-pow2(min 4) padding in DenseRPQ._pad_width",
                f"_pad_width:{S}:{w}"))
    return findings


# ---------------------------------------------------------------------
# T004: retrace audit on a canonical workload
# ---------------------------------------------------------------------

def _run_canonical(kind: str) -> Tuple[int, int]:
    """(signatures after first pass, new signatures on replay)."""
    from ..core import fixtures
    from ..core.engines import eval_many, make_engine

    eng = make_engine(fixtures.metro_graph(), kind=kind)
    eval_many(eng, list(CANONICAL_QUERIES))
    first = eng.traces.retraces
    eval_many(eng, list(CANONICAL_QUERIES))
    return first, eng.traces.retraces - first


def check_retraces() -> List[Finding]:
    findings: List[Finding] = []
    anchors = {"dense": "src/repro/core/dense.py",
               "ring": "src/repro/core/rpq.py"}
    for kind, budget in RETRACE_BUDGET.items():
        first, replay_new = _run_canonical(kind)
        if first > budget:
            findings.append(Finding(
                anchors[kind], 0, "T004",
                f"{kind} engine: canonical workload produced {first} "
                f"distinct jit signatures (budget {budget}) — dispatch "
                "shapes are fragmenting",
                "bucket/pad dispatch shapes so mixed queries share "
                "compiled signatures; see QueryStats.retraces",
                f"{kind}:retraces:{first}>{budget}"))
        if replay_new != 0:
            findings.append(Finding(
                anchors[kind], 0, "T004",
                f"{kind} engine: replaying the identical workload added "
                f"{replay_new} NEW jit signatures — signature keys are "
                "unstable (nondeterministic key material?)",
                "make dispatch signature keys a pure function of query "
                "shapes", f"{kind}:replay:{replay_new}"))
    return findings


# ---------------------------------------------------------------------
# T005: collective-bytes vs the planner wire model
# ---------------------------------------------------------------------

def check_collective_bytes(notes: List[str]) -> List[Finding]:
    from jax.sharding import Mesh

    from ..core import distributed as dist
    from ..launch.hlo_analysis import collective_bytes

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        notes.append(
            "T005 collective-bytes check skipped: needs >= 2 devices "
            f"(have {n}); run with --force-host-devices 8 or under the "
            "CI multidevice job")
        return []

    i8, i32 = jnp.int8, jnp.int32
    R, S, L = 4, 8, 3
    Vp = 32 * n
    Emax = 64 * n
    mesh = Mesh(np.array(devs), ("data",))
    step = dist.make_superstep_batched(mesh, ("data",))
    args = (_sds((R, Vp, S), i8), _sds((R, Vp, S), i8),
            _sds((n, Emax // n), i32), _sds((n, Emax // n), i32),
            _sds((n, Emax // n), i32),
            _sds((R, L + 1, S), i8), _sds((R, S, S), i8))
    try:
        with mesh:
            hlo = jax.jit(step).lower(*args).compile().as_text()
    except Exception as exc:  # noqa: BLE001
        return [Finding(
            "src/repro/core/distributed.py", 0, "T006",
            f"sharded superstep failed to compile for the collective "
            f"audit: {type(exc).__name__}: {exc}", "",
            "superstep:compile-failure")]

    stats = collective_bytes(hlo)
    gather = stats.bytes_by_kind.get("all-gather", 0.0)
    # Planner wire model: one frontier all-gather of [R, Vp, S] int8 per
    # superstep, wire bytes = size * (n-1)/n per participant.
    model = R * Vp * S * (n - 1) / n
    limit = model * COLLECTIVE_TOLERANCE + COLLECTIVE_SLACK_BYTES
    if gather > limit:
        return [Finding(
            "src/repro/core/distributed.py", 0, "T005",
            f"sharded batched superstep moves {gather:.0f} all-gather "
            f"bytes/participant; planner wire model predicts {model:.0f} "
            f"(limit {limit:.0f}, n={n}) — an extra or widened collective "
            "crept into the step",
            "the frontier gather must be the ONLY collective; check for "
            "accidental replication or dtype widening of gathered "
            "operands", f"superstep:all-gather:{n}")]
    if gather == 0.0:
        notes.append(
            f"T005: no all-gather found in compiled superstep HLO (n={n}); "
            "XLA may have rewritten the collective — wire model not "
            "comparable this build")
    else:
        notes.append(
            f"T005 OK: all-gather {gather:.0f} B/participant vs model "
            f"{model:.0f} B (n={n}, tolerance {COLLECTIVE_TOLERANCE}x)")
    return []


# ---------------------------------------------------------------------
# driver + lowering cache
# ---------------------------------------------------------------------

def _check_collective(notes: List[str]) -> List[Finding]:
    return check_collective_bytes(notes)


def _no_notes(fn: Callable[[], List[Finding]]
              ) -> Callable[[List[str]], List[Finding]]:
    return lambda notes: fn()


# (name, check(notes) -> findings, repo-relative source deps).  The dep
# sets are what each check lowers: editing any listed file (or any file
# under a listed directory) invalidates that check's cache entry only.
CHECKS: Tuple[Tuple[str, Callable[[List[str]], List[Finding]],
                    Tuple[str, ...]], ...] = (
    ("kernel_contracts", _no_notes(check_kernel_contracts),
     ("src/repro/kernels",)),
    ("hetero_bfs", _no_notes(check_hetero_bfs),
     ("src/repro/kernels", "src/repro/core/dense.py")),
    ("sharded_steps", _no_notes(check_sharded_steps),
     ("src/repro/kernels", "src/repro/core/distributed.py",
      "src/repro/launch")),
    ("pow2_padding", _no_notes(check_pow2_padding),
     ("src/repro/core/dense.py",)),
    ("retraces", _no_notes(check_retraces),
     ("src/repro/core", "src/repro/kernels")),
    ("collective_bytes", _check_collective,
     ("src/repro/kernels", "src/repro/core/distributed.py",
      "src/repro/launch")),
)

DEFAULT_CACHE_DIR = Path(".cache/repro-analysis")


def cache_key(root: Path, name: str, deps: Sequence[str]) -> Optional[str]:
    """Content hash over a check's source dependencies plus the jax /
    device signature.  ``None`` when no dep file resolves (running
    outside a source checkout) — such a check is uncacheable."""
    h = hashlib.sha256()
    h.update(f"{name}:{jax.__version__}:{jax.default_backend()}:"
             f"{len(jax.devices())}".encode())
    seen = 0
    for dep in deps:
        base = Path(root) / dep
        files = sorted(base.rglob("*.py")) if base.is_dir() else \
            [base] if base.is_file() else []
        for path in files:
            h.update(path.name.encode())
            h.update(path.read_bytes())
            seen += 1
    return h.hexdigest() if seen else None


def _run_checks_cached(
    root: Path,
    checks: Sequence[Tuple[str, Callable[[List[str]], List[Finding]],
                           Sequence[str]]],
    cache_dir: Optional[Path],
    use_cache: bool,
) -> Tuple[List[Finding], List[str], int, int]:
    """Run ``checks`` through the lowering cache.  Returns
    (findings, notes, hits, misses)."""
    cache_path = None
    cache: Dict[str, Dict] = {}
    if use_cache:
        cache_path = Path(cache_dir or Path(root) / DEFAULT_CACHE_DIR)
        cache_path = cache_path / "trace_audit.json"
        if cache_path.exists():
            try:
                cache = json.loads(cache_path.read_text())
            except (ValueError, OSError):
                cache = {}
    findings: List[Finding] = []
    notes: List[str] = []
    hits = misses = 0
    dirty = False
    for name, fn, deps in checks:
        key = cache_key(root, name, deps) if use_cache else None
        entry = cache.get(key) if key else None
        if entry is not None and entry.get("check") == name:
            findings += [Finding(**f) for f in entry["findings"]]
            notes += list(entry["notes"])
            hits += 1
            continue
        local_notes: List[str] = []
        got = fn(local_notes)
        findings += got
        notes += local_notes
        misses += 1
        if key:
            cache[key] = {"check": name,
                          "findings": [asdict(f) for f in got],
                          "notes": local_notes}
            dirty = True
    if dirty and cache_path is not None:
        # keep entries for other device/version signatures, but drop
        # superseded keys of the checks just re-run so the file does
        # not grow without bound as sources churn
        fresh_names = {name for name, _, _ in checks}
        live_keys = {cache_key(root, name, deps)
                     for name, _, deps in checks}
        cache = {k: v for k, v in cache.items()
                 if k in live_keys or v.get("check") not in fresh_names}
        cache_path.parent.mkdir(parents=True, exist_ok=True)
        cache_path.write_text(json.dumps(cache, indent=1) + "\n")
    return findings, notes, hits, misses


def run_trace_audit(root: Path = Path("."), *,
                    cache_dir: Optional[Path] = None,
                    use_cache: bool = True
                    ) -> Tuple[List[Finding], List[str]]:
    """All trace-audit checks.  Returns (findings, human-readable
    notes).  The audit runs against the *imported* package; ``root`` is
    only used to locate the source files that key (and the directory
    that stores) the lowering cache."""
    findings, notes, hits, misses = _run_checks_cached(
        root, CHECKS, cache_dir, use_cache)
    notes.append(f"trace-audit lowering cache: {hits} hit(s), "
                 f"{misses} miss(es)"
                 if use_cache else "trace-audit lowering cache: disabled")
    notes.append(f"trace audit ran on {len(jax.devices())} "
                 f"{jax.default_backend()} device(s)")
    return findings, notes
