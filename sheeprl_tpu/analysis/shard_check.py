"""sheepshard: SPMD partitioning & collective-communication analysis over
the lowered CompilePlan.

sheepcheck (jaxpr_check.py) audits every registered jit at the jaxpr level,
but the jaxpr is the program BEFORE XLA's SPMD partitioner runs — it is
blind to the thing that actually decides TPU scaling: how each jit shards
over the mesh and what collectives GSPMD inserts. Podracer
(arXiv:2104.06272) and MSRL (arXiv:2210.00882) both show that TPU-RL
throughput is won or lost in the placement/communication structure. This
module closes that gap: every mesh-bearing registered jit is lowered AND
compiled under its declared mesh (CPU, the virtual 8-device harness, zero
execution — `lower().compile()` builds the partitioned module without
running it), and the post-partitioning HLO text is parsed into a per-jit
**comms ledger**: every collective op (all-reduce / all-gather /
reduce-scatter / collective-permute / all-to-all), its operand/result
bytes, replica groups, whether it sits inside a while/scan body (where it
multiplies by the trip count), and an estimated bytes-on-the-wire per
dispatch under a ring-algorithm model.

Rule catalog (continues sheepcheck's SC numbering; suppressions live in
`SHARD_SUPPRESSIONS`, keyed `(spec, jit, rule)`, justification mandatory —
SC009 is source-level and uses sheeplint's `# sheeplint: disable=SC009`
comment syntax instead):

  SC006  collective inside a hot-loop (while/scan) body of a registered
         jit — the while's trip count multiplies the per-step comms; a
         gradient all-reduce per minibatch is a design decision that must
         be visible (and suppressed with its justification), an accidental
         one is a scaling cliff.
  SC007  silent full replication — an input the example thunk left
         UNSPECIFIED (no committed sharding) that the partitioner chose to
         fully replicate over a >1-device mesh, above a size floor:
         wasted HBM on every device plus an all-gather-shaped transfer on
         update. Declared (committed P()) replication is intentional and
         exempt — the rule targets layouts nobody chose.
  SC008  resharding thrash on a declared CompilePlan data edge — the
         producer jit's compiled output sharding disagrees with the
         consumer jit's compiled input sharding on an `expect="match"`
         edge, so every handoff pays an implicit reshard. This cross-jit
         contract check is the first concrete slice of the ROADMAP-4
         fragment graph.
  SC009  collective issued from an un-jitted host loop — an eager
         `jax.lax.psum`-family or `multihost_utils` call lexically inside
         a Python loop and outside any jit context pays one dispatch of a
         one-collective program per iteration (source-level AST pass,
         shares sheeplint's engine).

Fingerprints (collective histogram, hot-loop histogram, wire bytes,
silently-replicated inputs, per-edge sharding contracts) are committed to
the `analysis/budget/` ledger next to sheepcheck's compile-cost
fingerprints, and `tools/sheepshard.py --check-budget` is the CI drift
gate: a new collective kind, a new/multiplied hot-loop collective,
comms-bytes growth past tolerance, a newly replicated large tensor, or a
match-edge flipping to mismatch fails the build; reductions are notes.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Any, Iterable, Iterator

from .rules import Rule
from . import jaxpr_check as jc

__all__ = [
    "SHARD_RULES",
    "SHARD_SUPPRESSIONS",
    "SHARD_SWEEP",
    "Collective",
    "ShardReport",
    "analyze_entry",
    "analyze_shard_plan",
    "build_comms_budget",
    "check_comms_budget",
    "check_source_collectives",
    "comms_fingerprint",
    "estimate_wire_bytes",
    "parse_hlo_comms",
    "resolve_capture",
    "resolve_edges",
    "spec_key",
]

ERROR = "error"
WARNING = "warning"

_SHARD_RULES = [
    Rule(
        id="SC006",
        name="collective-in-hot-loop",
        severity=WARNING,
        summary=(
            "collective op inside a while/scan body of a registered jit — "
            "the loop's trip count multiplies the per-step communication, "
            "so one all-gather in a rollout scan is T all-gathers per "
            "dispatch"
        ),
        autofix=(
            "restructure so the collective runs once outside the loop "
            "(reduce locally, combine after the scan), or suppress with "
            "the design justification (a per-minibatch gradient all-reduce "
            "is the data-parallel minimum)"
        ),
    ),
    Rule(
        id="SC007",
        name="silent-full-replication",
        severity=WARNING,
        summary=(
            "large input with NO declared sharding that the SPMD "
            "partitioner fully replicated over a multi-device mesh — "
            "every device holds the whole tensor (wasted HBM) and updates "
            "pay replication traffic nobody asked for"
        ),
        autofix=(
            "commit the array with an explicit sharding (shard_batch / "
            "shard_env_batch / NamedSharding on the example spec), or make "
            "the replication explicit with a committed P() so the ledger "
            "records it as chosen"
        ),
    ),
    Rule(
        id="SC008",
        name="resharding-thrash",
        severity=WARNING,
        summary=(
            "producer jit's output sharding disagrees with the consumer "
            "jit's input sharding on a declared expect='match' data edge — "
            "every handoff forces an implicit reshard (all-gather + "
            "re-slice) XLA inserts silently at dispatch"
        ),
        autofix=(
            "align the two jits' shardings (usually: make the consumer's "
            "example thunk carry the producer's output sharding), or "
            "declare the edge expect='reshard' if the reshuffle is the "
            "documented contract"
        ),
    ),
    Rule(
        id="SC009",
        name="collective-in-host-loop",
        severity=WARNING,
        summary=(
            "eager collective (jax.lax.psum family / multihost_utils) "
            "called from an un-jitted Python loop — each iteration "
            "dispatches a one-collective program with full host-side "
            "dispatch overhead"
        ),
        autofix=(
            "move the loop under jit (lax.scan/fori_loop) so the "
            "collectives fuse into one program, or hoist the collective "
            "out of the loop; suppress with `# sheeplint: disable=SC009` "
            "plus justification for intentional per-iteration syncs"
        ),
    ),
]

SHARD_RULES: dict[str, Rule] = {r.id: r for r in _SHARD_RULES}

# (spec, jit, rule) -> justification; same contract as jaxpr_check's
# SUPPRESSIONS: a matching finding is reported as suppressed, not failing,
# and the justification is printed in verbose output.
SHARD_SUPPRESSIONS: dict[tuple[str, str, str], str] = {
    # The PPO update scans epochs x minibatches INSIDE one jit; under data
    # parallelism each minibatch's gradient all-reduce therefore sits in
    # the scan body. That is the data-parallel minimum (one grad-sized
    # all-reduce per minibatch, same count as the reference's per-step DDP
    # all-reduce) — the ledger locks the histogram so any ADDITIONAL
    # hot-loop collective still fails the gate.
    ("ppo@mesh8", "train_step", "SC006"): (
        "per-minibatch gradient all-reduce inside the epoch/minibatch scan "
        "is the data-parallel design minimum"
    ),
    ("ppo@anakin", "train_step", "SC006"): (
        "per-minibatch gradient all-reduce inside the epoch/minibatch scan "
        "is the data-parallel design minimum"
    ),
    # Under context parallelism the imagination scan runs over [T*B] rows
    # sharded across the FULL (data, seq) grid (the replicated-RSSM layout,
    # which spares the backward pass a full rematerialization of the scan
    # input: parallel/mesh.py `scan_batch_spec`), so its per-step actor/head
    # reductions all-reduce across the grid inside the scan body by
    # construction. The ledger locks the hot histogram: any ADDITIONAL
    # hot-loop collective still fails the comms gate.
    ("dreamer_v3@seq", "train_step", "SC006"): (
        "imagination-scan reductions over the fully-grid-sharded [T*B] "
        "rows are the chosen context-parallel layout"
    ),
}

_COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

# ---------------------------------------------------------------------------
# HLO text parsing: computations, loop bodies, collective instructions
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s32": 4, "u32": 4, "f32": 4, "c64": 8,
    "s64": 8, "u64": 8, "f64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(
    r"\b(pred|f8e4m3fn|f8e5m2|f8e4m3b11fnuz|bf16|f16|f32|f64|c64|c128|"
    r"s4|s8|s16|s32|s64|u4|u8|u16|u32|u64)\[([0-9,]*)\]"
)

# `%name (params) -> result {` and `ENTRY %name (params) -> result {`
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")

_COLL_RE = re.compile(
    r"=\s*(?P<rtype>\([^=]*?\)|\S+)\s+"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\("
)

_CALLED_RE = re.compile(r"(?:to_apply|body|condition|calls)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count.{0,4}?[":{n]*"?(\d+)"')


def _shape_bytes(text: str) -> int:
    """Sum of array bytes over every `dtype[dims]` token in `text` (a type
    string — handles tuple types by summing elements)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def _replica_groups(attrs: str, num_partitions: int) -> tuple[int, int]:
    """Parse `replica_groups` in either syntax into (groups, group_size):
    the iota form `[G,S]<=[N]` or the explicit `{{0,1},{2,3}}` form."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]", attrs)
    if m:
        return int(m.group(1)), int(m.group(2))
    m = re.search(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}", attrs)
    if m:
        groups = m.group(1).split("},{")
        sizes = [
            len([t for t in g.strip("{}").split(",") if t.strip()]) for g in groups
        ]
        return len(groups), (max(sizes) if sizes else 1)
    return 1, max(num_partitions, 1)


def estimate_wire_bytes(
    kind: str, result_bytes: int, operand_bytes: int, groups: int, group_size: int
) -> int:
    """Estimated total bytes crossing the interconnect per dispatch of one
    collective, ring-algorithm model. HLO shapes are per-participant, and
    the LARGER of operand/result is the full logical payload (all-gather's
    result, reduce-scatter's operand, all-reduce's both):

      all-reduce      2*(s-1)*B   (reduce-scatter + all-gather phases)
      all-gather        (s-1)*B   (each device receives (s-1)/s of B)
      reduce-scatter    (s-1)*B   (mirror of all-gather)
      all-to-all        (s-1)*B   (each device keeps 1/s of its buffer)
      collective-permute  s * B   (each participant ships its buffer)

    multiplied by the number of disjoint replica groups."""
    full = max(result_bytes, operand_bytes)
    s = max(group_size, 1)
    if kind == "all-reduce":
        per_group = 2 * (s - 1) * full
    elif kind == "collective-permute":
        per_group = s * full
    else:
        per_group = (s - 1) * full
    return max(groups, 1) * per_group


@dataclasses.dataclass
class Collective:
    """One collective instruction of a partitioned HLO module."""

    kind: str
    name: str
    result_bytes: int
    operand_bytes: int
    groups: int
    group_size: int
    wire_bytes: int  # per dispatch of the enclosing computation
    hot: bool = False  # inside a while/scan body computation
    trip_count: int | None = None  # known_trip_count of the enclosing loop

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def parse_hlo_comms(text: str) -> dict:
    """Parse a post-partitioning HLO module (Compiled.as_text()) into its
    communication structure: `num_partitions`, and every collective with
    bytes, replica groups, and hot-loop placement (a collective is `hot`
    when its computation is reachable from a `while` body/condition —
    loop trip counts from XLA's `known_trip_count` when printed)."""
    lines = text.splitlines()
    header = lines[0] if lines else ""
    m = re.search(r"num_partitions=(\d+)", header)
    num_partitions = int(m.group(1)) if m else 1

    comps: dict[str, list[str]] = {}
    cur: str | None = None
    for line in lines[1:]:
        hdr = _COMP_HDR_RE.match(line)
        if hdr is not None:
            cur = hdr.group(1)
            comps[cur] = []
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)

    called: dict[str, set[str]] = {name: set() for name in comps}
    loop_roots: list[tuple[str, int | None]] = []  # (body/cond comp, trip)
    for name, body in comps.items():
        for line in body:
            refs = set(_CALLED_RE.findall(line))
            for blob in _BRANCHES_RE.findall(line):
                refs |= {b.strip().lstrip("%") for b in blob.split(",") if b.strip()}
            called[name] |= refs & set(comps)
            if " while(" in line:
                trip_m = _TRIP_RE.search(line)
                trip = int(trip_m.group(1)) if trip_m else None
                for key in ("body", "condition"):
                    km = re.search(rf"{key}=%?([\w.\-]+)", line)
                    if km and km.group(1) in comps:
                        loop_roots.append((km.group(1), trip))

    # transitive closure: everything reachable from a loop body is hot;
    # keep the largest known trip count on the path (0 = unknown)
    hot_trip: dict[str, int] = {}
    stack = [(name, trip or 0) for name, trip in loop_roots]
    while stack:
        name, trip = stack.pop()
        if name in hot_trip and hot_trip[name] >= trip:
            continue
        hot_trip[name] = trip
        for callee in called.get(name, ()):
            stack.append((callee, trip))

    collectives: list[Collective] = []
    for name, body in comps.items():
        hot = name in hot_trip
        trip = hot_trip.get(name) or None
        for line in body:
            m = _COLL_RE.search(line)
            if m is None:
                continue
            rest = line[m.end():]
            depth = 1
            for i, ch in enumerate(rest):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
            operands, attrs = rest[:i], rest[i + 1:]
            kind = m.group("kind")
            if kind == "collective-permute":
                pairs = re.search(r"source_target_pairs=\{(.*?)\}\}", attrs)
                npairs = pairs.group(1).count("{") + 1 if pairs else num_partitions
                groups, group_size = npairs, 1
            else:
                groups, group_size = _replica_groups(attrs, num_partitions)
            result_bytes = _shape_bytes(m.group("rtype"))
            operand_bytes = _shape_bytes(operands)
            collectives.append(
                Collective(
                    kind=kind,
                    name=name,
                    result_bytes=result_bytes,
                    operand_bytes=operand_bytes,
                    groups=groups,
                    group_size=group_size,
                    wire_bytes=estimate_wire_bytes(
                        kind, result_bytes, operand_bytes, groups, group_size
                    ),
                    hot=hot,
                    trip_count=trip,
                )
            )
    return {"num_partitions": num_partitions, "collectives": collectives}


# ---------------------------------------------------------------------------
# sharding introspection
# ---------------------------------------------------------------------------


def spec_key(sharding: Any) -> str:
    """A stable, human-readable key for a sharding: 'unspecified',
    'replicated', or `P(spec)@(mesh axes)` — what the ledger commits and
    the SC008 contract compares."""
    if sharding is None:
        return "unspecified"
    if sharding is _UNUSED:
        return "unused"
    if getattr(sharding, "is_fully_replicated", False):
        return "replicated"
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if spec is not None and mesh is not None:
        axes = ",".join(f"{k}={v}" for k, v in dict(mesh.shape).items())
        return f"P{tuple(spec)}@({axes})"
    # GSPMDSharding (what the partitioner reports for inputs nobody
    # declared): the HLO tile assignment is the readable, stable part
    hlo = getattr(sharding, "_hlo_sharding", None)
    if hlo is not None:
        return f"hlo:{hlo}"
    return repr(sharding)[:120]


_UNUSED = object()  # flat input dropped by XLA's dead-arg elimination


def _flat_input_shardings(compiled: Any, n: int) -> list[Any]:
    """The compiled executable's per-flat-argument shardings, length `n`
    (the jaxpr's flat arity). XLA prunes unused arguments and
    `input_shardings` covers only the kept ones, so dropped positions are
    re-aligned via the executable's kept_var_idx and marked `_UNUSED` (an
    unused input imposes no layout constraint). None = introspection
    failed."""
    import jax

    try:
        args_sh, _ = compiled.input_shardings
        flat = list(jax.tree_util.tree_leaves(args_sh))
    except Exception:
        return [None] * n
    if len(flat) == n:
        return flat
    kept = getattr(getattr(compiled, "_executable", None), "_kept_var_idx", None)
    if kept is not None and len(kept) == len(flat):
        out: list[Any] = [_UNUSED] * n
        for idx, sh in zip(sorted(kept), flat):
            if idx < n:
                out[idx] = sh
        return out
    return [None] * n


def _flat_output_shardings(compiled: Any, n: int) -> list[Any]:
    import jax

    try:
        flat = list(jax.tree_util.tree_leaves(compiled.output_shardings))
    except Exception:
        flat = []
    if len(flat) != n:
        return [None] * n
    return flat


def _declared_shardings(specs: Any) -> list[Any]:
    """Per-flat-leaf sharding the example thunk DECLARED (None for leaves
    the main left unspecified — python scalars, uncommitted arrays)."""
    import jax

    out = []
    for leaf in jax.tree_util.tree_leaves(specs):
        out.append(getattr(leaf, "sharding", None))
    return out


def _mesh_axes(shardings: Iterable[Any]) -> dict[str, int]:
    """The (first) multi-device mesh named by any declared sharding."""
    for s in shardings:
        mesh = getattr(s, "mesh", None)
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    return {}


def _replicated_floor() -> int:
    try:
        return int(
            os.environ.get("SHEEPRL_TPU_SHARD_REPLICATED_FLOOR", str(1 << 20))
        )
    except ValueError:
        return 1 << 20


def _aval_bytes(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(getattr(dtype, "itemsize", 4))


# ---------------------------------------------------------------------------
# per-entry analysis
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardReport:
    spec: str
    name: str
    comms: dict | None = None  # the committed comms fingerprint
    in_avals: list[str] = dataclasses.field(default_factory=list)
    out_avals: list[str] = dataclasses.field(default_factory=list)
    in_specs: list[str] = dataclasses.field(default_factory=list)
    out_specs: list[str] = dataclasses.field(default_factory=list)
    in_declared: list[str] = dataclasses.field(default_factory=list)
    findings: list[jc.Finding] = dataclasses.field(default_factory=list)
    error: str | None = None  # not analyzable / not mesh-bearing
    # live sharding objects (NOT committed to the ledger): the SC008
    # contract compares these semantically — a GSPMDSharding the partitioner
    # picked and the NamedSharding a producer declared stringify differently
    # but can be the same layout (Sharding.is_equivalent_to)
    in_shardings: list = dataclasses.field(default_factory=list, repr=False)
    out_shardings: list = dataclasses.field(default_factory=list, repr=False)
    in_ndims: list[int] = dataclasses.field(default_factory=list, repr=False)
    out_ndims: list[int] = dataclasses.field(default_factory=list, repr=False)

    @property
    def failing(self) -> list[jc.Finding]:
        return [f for f in self.findings if not f.suppressed]


def comms_fingerprint(
    parsed: dict, declared: list[Any], compiled_in: list[Any], in_avals: list[Any]
) -> dict:
    """The committed per-jit comms fingerprint: what the ledger stores and
    `check_comms_budget` gates. `wire_bytes` counts hot collectives times
    their known trip count (per dispatch of the whole jit)."""
    hist: dict[str, int] = {}
    hot_hist: dict[str, int] = {}
    wire = 0
    wire_hot = 0
    for c in parsed["collectives"]:
        hist[c.kind] = hist.get(c.kind, 0) + 1
        multiplier = (c.trip_count or 1) if c.hot else 1
        contrib = c.wire_bytes * multiplier
        wire += contrib
        if c.hot:
            hot_hist[c.kind] = hot_hist.get(c.kind, 0) + 1
            wire_hot += contrib
    floor = _replicated_floor()
    replicated_inputs: list[str] = []
    replicated_bytes = 0
    for i, (decl, comp, aval) in enumerate(zip(declared, compiled_in, in_avals)):
        if decl is not None:
            continue  # declared layouts are chosen, not silent
        if not getattr(comp, "is_fully_replicated", False):
            continue
        nbytes = _aval_bytes(aval)
        replicated_bytes += nbytes
        if nbytes >= floor:
            replicated_inputs.append(f"{i}:{jc._aval_str(aval)}")
    return {
        "num_partitions": int(parsed["num_partitions"]),
        "mesh": _mesh_axes(declared),
        "collectives": dict(sorted(hist.items())),
        "hot_collectives": dict(sorted(hot_hist.items())),
        "wire_bytes": int(wire),
        "wire_bytes_hot": int(wire_hot),
        "replicated_inputs": sorted(replicated_inputs),
        "replicated_bytes": int(replicated_bytes),
    }


def _fmt_bytes(n: int) -> str:
    if n >= 1 << 20:
        return f"{n / (1 << 20):.1f}MiB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f}KiB"
    return f"{n}B"


def analyze_entry(
    spec: str,
    entry: Any,
    rules: set[str] | None = None,
    force: bool = False,
) -> tuple[ShardReport, Any | None]:
    """Lower-and-compile one CompilePlan entry under its declared mesh and
    analyze the partitioned module (SC006/SC007 + the comms fingerprint).
    Entries whose example declares no multi-device sharding are skipped as
    not mesh-bearing unless `force` (edge endpoints are forced so SC008
    can compare both ends). Returns `(report, compiled)`."""
    from ..compile.plan import avals_of

    report = ShardReport(spec=spec, name=entry.name)
    fn, example = entry.fn, entry.example
    if example is None:
        report.error = "no example thunk (registered for timing only)"
        return report, None
    if not hasattr(fn, "trace") or not hasattr(fn, "lower"):
        report.error = "not traceable (wrapped callable without .trace/.lower)"
        return report, None
    try:
        specs = avals_of(example())
        declared = _declared_shardings(specs)
    except Exception as err:
        report.error = f"example failed: {type(err).__name__}: {err}"[:300]
        return report, None
    mesh_bearing = bool(_mesh_axes(declared))
    if not mesh_bearing and not force:
        report.error = "not mesh-bearing (no multi-device sharding declared)"
        return report, None
    try:
        traced = fn.trace(*specs)
        closed = traced.jaxpr
        compiled = traced.lower().compile()
    except Exception as err:
        report.error = f"lower/compile failed: {type(err).__name__}: {err}"[:300]
        return report, None

    in_avals = [v.aval for v in closed.jaxpr.invars]
    out_avals = [v.aval for v in closed.jaxpr.outvars]
    compiled_in = _flat_input_shardings(compiled, len(in_avals))
    compiled_out = _flat_output_shardings(compiled, len(out_avals))
    report.in_avals = [jc._aval_str(a) for a in in_avals]
    report.out_avals = [jc._aval_str(a) for a in out_avals]
    report.in_specs = [spec_key(s) for s in compiled_in]
    report.out_specs = [spec_key(s) for s in compiled_out]
    report.in_declared = [spec_key(s) for s in declared]
    report.in_shardings = compiled_in
    report.out_shardings = compiled_out
    report.in_ndims = [len(getattr(a, "shape", ())) for a in in_avals]
    report.out_ndims = [len(getattr(a, "shape", ())) for a in out_avals]

    parsed = parse_hlo_comms(compiled.as_text())
    report.comms = comms_fingerprint(parsed, declared, compiled_in, in_avals)

    def emit(rule_id: str, message: str) -> None:
        if rules is not None and rule_id not in rules:
            return
        finding = jc.Finding(SHARD_RULES[rule_id], spec, entry.name, message)
        finding.suppressed = SHARD_SUPPRESSIONS.get((spec, entry.name, rule_id))
        report.findings.append(finding)

    for c in parsed["collectives"]:
        if c.hot:
            trip = f" x{c.trip_count} loop iterations" if c.trip_count else ""
            emit(
                "SC006",
                f"{c.kind} ({_fmt_bytes(c.wire_bytes)} on the wire per "
                f"dispatch{trip}) inside while/scan body `{c.name}` — "
                "per-step comms multiply by the trip count",
            )
    for item in report.comms["replicated_inputs"]:
        idx, aval = item.split(":", 1)
        emit(
            "SC007",
            f"input {idx} ({aval}) was left unspecified and the "
            f"partitioner fully replicated it over the "
            f"{report.comms['num_partitions']}-device mesh — "
            "silent replication (wasted HBM + replication traffic); "
            "commit it with an explicit sharding",
        )
    return report, compiled


# ---------------------------------------------------------------------------
# data-edge contracts (SC008)
# ---------------------------------------------------------------------------


def _same_layout(
    s_obj: Any, s_key: str, d_obj: Any, d_key: str, ndim: int
) -> bool:
    """Producer/consumer sharding equality: string keys first, then the
    semantic check — a GSPMDSharding the partitioner picked for an
    undeclared input and the NamedSharding the producer declared stringify
    differently but can be the identical layout."""
    if s_key == d_key:
        return True
    if (
        hasattr(s_obj, "is_equivalent_to")
        and hasattr(d_obj, "is_equivalent_to")
    ):
        try:
            return d_obj.is_equivalent_to(s_obj, ndim)
        except Exception:
            return False
    return False


def _auto_pairs(
    src_report: ShardReport, dst_report: ShardReport
) -> dict[str, tuple[list[str], list[str], list[str]]]:
    """Match producer outputs to consumer inputs by (shape, dtype) group.
    Positional pairing across two separately flattened pytrees is not
    recoverable in general, so the check is over aval groups — and only
    over the consumer inputs whose example DECLARED no layout: a declared
    sharding is a chosen contract (and the WarmJit aval check enforces it
    live), while an undeclared input's compiled sharding is whatever the
    partitioner picked — exactly where silent producer/consumer drift
    hides (and how tiny-width param shapes colliding with batch shapes
    stay out of the comparison). Returns aval -> (src_keys, dst_keys,
    unmatched_dst_keys): a group mismatches when some silent consumer
    sharding is layout-equal to NO producer sharding for that aval."""
    src_by_aval: dict[str, list[tuple[str, Any]]] = {}
    for i, (aval, sk) in enumerate(
        zip(src_report.out_avals, src_report.out_specs)
    ):
        obj = (
            src_report.out_shardings[i]
            if i < len(src_report.out_shardings) else None
        )
        src_by_aval.setdefault(aval.rstrip("~"), []).append((sk, obj))
    dst_by_aval: dict[str, list[tuple[str, Any, int]]] = {}
    for i, (aval, sk, declared) in enumerate(
        zip(dst_report.in_avals, dst_report.in_specs, dst_report.in_declared)
    ):
        if declared != "unspecified":
            continue  # declared layout: a chosen contract, not silent drift
        if sk in ("unused", "unspecified"):
            continue  # pruned by XLA / uninspectable: nothing to check
        obj = (
            dst_report.in_shardings[i]
            if i < len(dst_report.in_shardings) else None
        )
        ndim = dst_report.in_ndims[i] if i < len(dst_report.in_ndims) else 0
        dst_by_aval.setdefault(aval.rstrip("~"), []).append((sk, obj, ndim))
    out: dict[str, tuple[list[str], list[str], list[str]]] = {}
    for aval in sorted(set(src_by_aval) & set(dst_by_aval)):
        srcs = src_by_aval[aval]
        unmatched = sorted(
            {
                d_key
                for d_key, d_obj, ndim in dst_by_aval[aval]
                if not any(
                    _same_layout(s_obj, s_key, d_obj, d_key, ndim)
                    for s_key, s_obj in srcs
                )
            }
        )
        out[aval] = (
            sorted({sk for sk, _ in srcs}),
            sorted({dk for dk, _, _ in dst_by_aval[aval]}),
            unmatched,
        )
    return out


def resolve_edges(
    spec: str,
    edges: Iterable[Any],
    reports_by_name: dict[str, ShardReport],
    rules: set[str] | None = None,
) -> tuple[dict[str, dict], list[jc.Finding]]:
    """Resolve every declared DataEdge of one plan against the compiled
    shardings. Returns `(records, findings)`: records go to the ledger
    (keyed `src->dst`), SC008 findings fire on expect='match' mismatches."""
    records: dict[str, dict] = {}
    findings: list[jc.Finding] = []
    for edge in edges:
        src = reports_by_name.get(edge.src)
        dst = reports_by_name.get(edge.dst)
        rec: dict[str, Any] = {"expect": edge.expect}
        if edge.note:
            rec["note"] = edge.note
        if (
            src is None or dst is None
            or src.comms is None or dst.comms is None
        ):
            missing = edge.src if (src is None or src.comms is None) else edge.dst
            rec["status"] = "unresolved"
            rec["reason"] = f"{missing}: no compiled shardings"
            records[edge.key] = rec
            continue
        mismatched: dict[str, tuple[list[str], list[str]]] = {}
        contract: dict[str, dict] = {}
        if edge.pairs:
            for oi, ii in edge.pairs:
                try:
                    s_key, d_key = src.out_specs[oi], dst.in_specs[ii]
                    aval = src.out_avals[oi]
                except IndexError:
                    rec["status"] = "unresolved"
                    rec["reason"] = f"pair ({oi},{ii}) out of range"
                    break
                s_obj = (
                    src.out_shardings[oi]
                    if oi < len(src.out_shardings) else None
                )
                d_obj = (
                    dst.in_shardings[ii] if ii < len(dst.in_shardings) else None
                )
                ndim = dst.in_ndims[ii] if ii < len(dst.in_ndims) else 0
                contract[f"{aval}[{oi}->{ii}]"] = {"src": [s_key], "dst": [d_key]}
                if not _same_layout(s_obj, s_key, d_obj, d_key, ndim):
                    mismatched[f"{aval}[{oi}->{ii}]"] = ([s_key], [d_key])
            if rec.get("status") == "unresolved":
                records[edge.key] = rec
                continue
        else:
            for aval, (s_keys, d_keys, unmatched) in _auto_pairs(src, dst).items():
                contract[aval] = {"src": s_keys, "dst": d_keys}
                if unmatched:
                    mismatched[aval] = (s_keys, unmatched)
        rec["contract"] = contract
        rec["status"] = (
            "mismatch" if (mismatched and edge.expect == "match") else "ok"
        )
        records[edge.key] = rec
        if mismatched and edge.expect == "match":
            if rules is not None and "SC008" not in rules:
                continue
            detail = "; ".join(
                f"{aval}: {'/'.join(s)} -> {'/'.join(d)}"
                for aval, (s, d) in sorted(mismatched.items())
            )
            finding = jc.Finding(
                SHARD_RULES["SC008"],
                spec,
                edge.key,
                f"producer/consumer sharding contract broken on "
                f"{len(mismatched)} aval group(s): {detail} — every handoff "
                "pays an implicit reshard",
            )
            finding.suppressed = SHARD_SUPPRESSIONS.get(
                (spec, edge.key, "SC008")
            )
            findings.append(finding)
    return records, findings


def analyze_shard_plan(
    spec: str, plan: Any, rules: set[str] | None = None
) -> tuple[list[ShardReport], dict[str, dict], list[jc.Finding]]:
    """Analyze one captured CompilePlan: every mesh-bearing entry (plus
    edge endpoints) is compiled and fingerprinted, then the declared data
    edges are resolved. Returns `(reports, edge_records, edge_findings)`."""
    edges = plan.edges
    endpoint_names = {e.src for e in edges} | {e.dst for e in edges}
    reports: list[ShardReport] = []
    by_name: dict[str, ShardReport] = {}
    for entry in plan._entries:
        report, _compiled = analyze_entry(
            spec, entry, rules=rules, force=entry.name in endpoint_names
        )
        reports.append(report)
        by_name[entry.name] = report
    edge_records, edge_findings = resolve_edges(spec, edges, by_name, rules=rules)
    return reports, edge_records, edge_findings


# ---------------------------------------------------------------------------
# SC009: eager collectives in host loops (source-level, sheeplint engine)
# ---------------------------------------------------------------------------

_EAGER_COLLECTIVE_LEAVES = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute", "pshuffle", "psum_scatter",
}
_MULTIHOST_LEAVES = {
    "process_allgather", "broadcast_one_to_all", "sync_global_devices",
}


def check_source_collectives(paths: Iterable[str]) -> list[jc.Finding]:
    """AST pass over `paths` for SC009: eager collective calls (jax.lax
    psum family, multihost_utils helpers) outside any jit context and
    lexically inside a Python loop. Suppressible with sheeplint's comment
    syntax (`# sheeplint: disable=SC009 — why`)."""
    from .linter import _FileAnalysis, _parse_suppressions, iter_python_files

    findings: list[jc.Finding] = []
    for path in iter_python_files(paths):
        try:
            with open(path, encoding="utf-8") as fh:
                src = fh.read()
            a = _FileAnalysis(src, path)
        except (OSError, SyntaxError, ValueError):
            continue
        per_line, file_level = _parse_suppressions(src)
        if "all" in file_level or "SC009" in file_level:
            continue
        for node in ast.walk(a.tree):
            if not isinstance(node, ast.Call):
                continue
            d = a._dotted(node.func)
            if d is None:
                continue
            root, _, leaf = d.rpartition(".")
            root_head = root.split(".", 1)[0]
            is_collective = (
                leaf in _EAGER_COLLECTIVE_LEAVES
                and (root_head in ("jax", "lax") or ".lax" in root)
            ) or (leaf in _MULTIHOST_LEAVES and "multihost" in d)
            if not is_collective or a._in_jit_context(node):
                continue
            in_loop = False
            for p in a._parents(node):
                if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                    break
                if isinstance(p, (ast.For, ast.While)):
                    in_loop = True
                    break
            if not in_loop:
                continue
            line = getattr(node, "lineno", 1)
            sup = per_line.get(line, set())
            if "all" in sup or "SC009" in sup:
                continue
            findings.append(
                jc.Finding(
                    SHARD_RULES["SC009"],
                    "<source>",
                    f"{path}:{line}",
                    f"eager `{d}` inside an un-jitted host loop — one "
                    "single-collective dispatch per iteration",
                )
            )
    return findings


# ---------------------------------------------------------------------------
# the sweep: mesh-bearing capture configurations
# ---------------------------------------------------------------------------

# spec -> (sheepcheck capture spec, extra argv APPENDED after it — later
# flags win). These define the mesh each comms fingerprint is derived
# under; they are part of the committed ledger's contract the same way
# CAPTURE_ARGV is for the compile-cost fingerprints. The virtual 8-mesh
# matches the tests/conftest + CI harness.
SHARD_SWEEP: dict[str, tuple[str, list[str]]] = {
    # data-parallel PPO on the full virtual 8-mesh: the per-minibatch
    # gradient all-reduce inside the epoch/minibatch scan
    "ppo@mesh8": ("ppo", ["--num_devices", "8", "--num_envs", "8"]),
    # the Anakin arrangement on the 8-mesh: env batch sharded over the
    # mesh, zero collectives inside the rollout scan by design, plus the
    # rollout->gae->train_step data edges
    "ppo@anakin": ("ppo@anakin", ["--num_devices", "8", "--num_envs", "8"]),
    # context parallelism: (data=4, seq=2) mesh — the seq-axis boundary
    # all-gathers around the RSSM scan. --train_every 8 keeps the dry-run
    # sequence clamp at the full T=8 window (the clamp floors T at
    # train_every/num_envs, and T=1 cannot shard over the seq axis).
    "dreamer_v3@seq": (
        "dreamer_v3",
        [
            "--num_devices", "8", "--seq_devices", "2",
            "--per_rank_batch_size", "4", "--train_every", "8",
        ],
    ),
    # Anakin Dreamer: sharded collectors + the device replay ring
    "dreamer_v3@anakin": ("dreamer_v3@anakin", ["--num_devices", "2", "--num_envs", "2"]),
    # decoupled player/trainer topologies: 1 player device + trainer mesh
    "ppo_decoupled@mesh": ("ppo_decoupled", ["--num_devices", "5"]),
    "sac_decoupled@mesh": ("sac_decoupled", ["--num_devices", "5"]),
    "dreamer_v3_decoupled@mesh": ("dreamer_v3_decoupled", ["--num_devices", "3"]),
}


def resolve_capture(spec: str) -> tuple[str, list[str]]:
    """Map a sheepshard sweep spec to `(algo, extra_argv)` for
    `jaxpr_check.capture_plan` — the sheepcheck capture/variant argv with
    the mesh overrides appended."""
    if spec in SHARD_SWEEP:
        base_spec, extra = SHARD_SWEEP[spec]
        algo, variant_argv = jc.resolve_capture(base_spec)
        return algo, [*variant_argv, *extra]
    return jc.resolve_capture(spec)


# ---------------------------------------------------------------------------
# comms ledger: build + drift gate
# ---------------------------------------------------------------------------


def build_comms_budget(
    reports: list[ShardReport],
    edges_by_spec: dict[str, dict[str, dict]],
    wire_bytes_frac: float = 0.25,
) -> dict:
    import jax

    return {
        "version": 1,
        "jax_version": jax.__version__,
        "tolerance": {"wire_bytes_frac": wire_bytes_frac},
        "comms": {
            f"{r.spec}/{r.name}": r.comms for r in reports if r.comms is not None
        },
        "edges": {
            f"{spec}/{key}": rec
            for spec, recs in sorted(edges_by_spec.items())
            for key, rec in sorted(recs.items())
        },
    }


def check_comms_budget(ledger: dict, derived: dict) -> tuple[list[str], list[str]]:
    """The CI comms drift gate. Failures are the ISSUE-gated classes: a
    new collective kind, a new or multiplied hot-loop collective,
    wire-bytes growth past tolerance, a newly replicated large tensor, a
    match-edge resolving to mismatch, and added/removed ledger entries.
    Reductions and contract improvements are notes."""
    failures: list[str] = []
    notes: list[str] = []
    tol = float(ledger.get("tolerance", {}).get("wire_bytes_frac", 0.25))
    old, new = ledger.get("comms", {}), derived.get("comms", {})
    for key in sorted(set(old) - set(new)):
        failures.append(f"{key}: comms fingerprint disappeared (ledger has it)")
    for key in sorted(set(new) - set(old)):
        failures.append(f"{key}: new mesh-bearing jit not in the comms ledger")
    for key in sorted(set(old) & set(new)):
        o, n = old[key], new[key]
        o_hist, n_hist = o.get("collectives", {}), n.get("collectives", {})
        new_kinds = sorted(set(n_hist) - set(o_hist))
        if new_kinds:
            failures.append(f"{key}: new collective kind(s) {new_kinds}")
        lost_kinds = sorted(set(o_hist) - set(n_hist))
        if lost_kinds:
            notes.append(f"{key}: collective kind(s) {lost_kinds} eliminated")
        o_hot, n_hot = o.get("hot_collectives", {}), n.get("hot_collectives", {})
        for kind in sorted(set(n_hot)):
            if n_hot[kind] > o_hot.get(kind, 0):
                failures.append(
                    f"{key}: hot-loop {kind} count grew "
                    f"{o_hot.get(kind, 0)} -> {n_hot[kind]} (collectives "
                    "inside while/scan bodies multiply per-step comms)"
                )
        for kind in sorted(set(o_hot)):
            if o_hot[kind] > n_hot.get(kind, 0):
                notes.append(
                    f"{key}: hot-loop {kind} count shrank "
                    f"{o_hot[kind]} -> {n_hot.get(kind, 0)}"
                )
        ow, nw = int(o.get("wire_bytes", 0)), int(n.get("wire_bytes", 0))
        if nw > ow * (1.0 + tol) and nw - ow > 1024:
            failures.append(
                f"{key}: comms bytes grew {ow} -> {nw} "
                f"(+{(nw - ow) / max(ow, 1):.0%}, tolerance {tol:.0%})"
            )
        elif nw < ow * (1.0 - tol) and ow - nw > 1024:
            notes.append(
                f"{key}: comms bytes shrank {ow} -> {nw} — refresh the ledger"
            )
        newly_replicated = sorted(
            set(n.get("replicated_inputs", [])) - set(o.get("replicated_inputs", []))
        )
        if newly_replicated:
            failures.append(
                f"{key}: newly replicated large tensor(s) {newly_replicated} "
                "— silent full replication under the sharded mesh"
            )
        dereplicated = sorted(
            set(o.get("replicated_inputs", [])) - set(n.get("replicated_inputs", []))
        )
        if dereplicated:
            notes.append(f"{key}: tensor(s) no longer replicated {dereplicated}")
    o_edges, n_edges = ledger.get("edges", {}), derived.get("edges", {})
    for key in sorted(set(o_edges) - set(n_edges)):
        failures.append(f"{key}: data edge disappeared (ledger has it)")
    for key in sorted(set(n_edges) - set(o_edges)):
        if n_edges[key].get("status") == "mismatch":
            failures.append(f"{key}: new data edge resolves to a sharding mismatch")
        else:
            failures.append(f"{key}: new data edge not in the ledger")
    for key in sorted(set(o_edges) & set(n_edges)):
        o_st, n_st = o_edges[key].get("status"), n_edges[key].get("status")
        if o_st == n_st:
            continue
        if n_st == "mismatch":
            failures.append(
                f"{key}: sharding contract broke ({o_st} -> mismatch) — "
                "every handoff now pays an implicit reshard"
            )
        else:
            notes.append(f"{key}: edge status changed {o_st} -> {n_st}")
    return failures, notes
