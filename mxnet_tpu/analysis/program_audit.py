"""Level-3 tpulint passes (TPL3xx) — audits over COMPILED XLA programs.

The speed thesis is whole-program XLA compilation (PAPER.md §compile
layer; arxiv 1810.09868), which moves the failure modes inside the
compiled artifact: PR 7 watched GSPMD silently inject stray all-gathers
into the ZeRO island, and ROADMAP item 5 wants per-axis comm bytes as a
first-class banked metric. TPL1xx sees source, TPL2xx sees jaxprs; this
pass family reads what the partitioner actually emitted.

For any ProgramBuilder entry (the ONE lower/compile/cache seam,
compile/builder.py — the audit reuses ``builder.lowered()``/``aot()``,
never a throwaway second trace) it extracts a **program contract**:

* the ordered multiset of collective HLO ops (all-reduce / all-gather /
  reduce-scatter / collective-permute / all-to-all) with result shapes
  and the MESH AXES their replica groups span;
* per-axis comm bytes (per-partition result-buffer bytes — the same
  convention as ``ZeroShardLayout.comm_bytes`` and the mesh-kernel
  rooflines, so the analytic ideals join directly);
* compiled-cost flops / bytes-accessed and the memory_analysis sizes
  (argument/output/temp, peak when the backend reports one);
* realized donation (``input_output_alias`` entries in the compiled
  module — declared donation the compiler didn't realize is a silent
  memory regression);
* program-family cardinality per site (ProgramBuilder keys, flagging
  weak_type/layout splits — silent cache bloat).

Rules::

    TPL301 stray-collective   collective not in the declared CommPlan /
                              committed manifest (the PR 7 hazard)
    TPL302 comm-drift         per-axis comm bytes beyond tolerance vs
                              the analytic ideal / manifest
    TPL303 program-family     family explosion: more programs than
                              declared, or weak_type-only key splits
    TPL304 memory-regression  peak/temp bytes growth or lost donation
                              aliasing vs the manifest

Contracts serialize to committed manifests under
``ci/program_manifests/*.json`` (one per core program) — diffed like a
sanitizer baseline by ``python -m mxnet_tpu.analysis.lint --audit`` and
the ``program_audit_smoke`` CI stage. ``--update-manifests`` re-pins
them (and regenerates docs/faq/comm_plans.md). Suppression rides the
existing findings machinery: a manifest unit may carry
``"allow": [{"slug": ..., "reason": ...}]`` entries — the reason is
REQUIRED (an empty one raises TPL000), exactly like source pragmas.

Env (read at tool entry only — never on dispatch paths):
``MXNET_TPU_AUDIT_TOL`` relative drift tolerance (default 0.25),
``MXNET_TPU_AUDIT_MANIFESTS`` manifest directory override.
"""
from __future__ import annotations

import json
import math
import os
import re

import numpy as _np

from .findings import Finding, Severity

__all__ = ["AUDIT_RULES", "CommPlan", "extract_contract", "family_stats",
           "parse_hlo_collectives", "audit_contract", "diff_contract",
           "manifest_path", "load_manifest", "write_manifest",
           "run_audit", "build_mispinned_zero_unit", "emit_comm_plans_doc",
           "CORE_PROGRAMS", "DEFAULT_TOLERANCE", "AuditUnit",
           "reference_mesh", "audit_tolerance", "manifest_dir"]

AUDIT_RULES = {
    "TPL301": ("stray-collective", Severity.ERROR,
               "collective HLO op not in the declared comm plan / "
               "committed manifest (partitioner-injected comm)"),
    "TPL302": ("comm-drift", Severity.ERROR,
               "per-axis comm bytes drifted beyond tolerance vs the "
               "analytic ideal / manifest"),
    "TPL303": ("program-family", Severity.ERROR,
               "program-family explosion: same site, keys differing only "
               "in weak_type/layout (silent cache bloat)"),
    "TPL304": ("memory-regression", Severity.ERROR,
               "peak-memory / donation regression vs the program "
               "manifest (declared donation left unrealized)"),
}

DEFAULT_TOLERANCE = 0.25

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _finding(rule_id, message, where, line=0):
    slug, sev, _ = AUDIT_RULES[rule_id]
    return Finding(rule_id, slug, sev, message, where, line)


def audit_tolerance(default=DEFAULT_TOLERANCE):
    """Relative drift tolerance — env read once at tool entry (the
    zero-overhead contract keeps dispatch paths env-free)."""
    from ..base import get_env
    return get_env("MXNET_TPU_AUDIT_TOL", default, float)


def manifest_dir(override=None):
    """Committed manifest directory (ci/program_manifests, overridable
    via MXNET_TPU_AUDIT_MANIFESTS — tool entry only)."""
    if override:
        return override
    from ..base import get_env
    return get_env("MXNET_TPU_AUDIT_MANIFESTS",
                   os.path.join(_REPO_ROOT, "ci", "program_manifests"))


# ---------------------------------------------------------------------------
# declared comm plans
# ---------------------------------------------------------------------------

class CommPlan:
    """What a program family DECLARES about its collectives.

    ``allowed`` entries are ``(op, axis)`` or ``(op, axis, max_count)``
    tuples — ``max_count=None`` means any count (XLA's collective
    combiner may merge per-leaf collectives, so counts are ceilings,
    never exact). ``ideal_bytes_per_axis`` joins the analytic byte
    accounting (ZeroShardLayout.comm_bytes, the mesh-kernel rooflines)
    for the TPL302 drift check; ``max_programs`` pins the family
    cardinality for TPL303 (e.g. len(buckets) for serving)."""

    def __init__(self, site="program", allowed=(), ideal_bytes_per_axis=None,
                 tolerance=None, max_programs=None):
        self.site = site
        self.allowed = []
        for ent in allowed or ():
            op, axis = ent[0], ent[1]
            max_count = ent[2] if len(ent) > 2 else None
            self.allowed.append((str(op), str(axis),
                                 None if max_count is None else int(max_count)))
        self.ideal_bytes_per_axis = dict(ideal_bytes_per_axis or {}) or None
        self.tolerance = tolerance
        self.max_programs = max_programs

    def allows(self, op, axis):
        """Max allowed count for (op, axis): an int, math.inf for an
        uncapped entry, or None when the pair is not in the plan."""
        best = None
        for aop, aaxis, amax in self.allowed:
            if aop == op and aaxis == axis:
                cap = math.inf if amax is None else amax
                best = cap if best is None else max(best, cap)
        return best

    def as_dict(self):
        return {"site": self.site,
                "allowed": [list(e) for e in self.allowed],
                "ideal_bytes_per_axis": self.ideal_bytes_per_axis,
                "tolerance": self.tolerance,
                "max_programs": self.max_programs}

    @classmethod
    def from_dict(cls, d):
        return cls(site=d.get("site", "program"),
                   allowed=[tuple(e) for e in d.get("allowed", ())],
                   ideal_bytes_per_axis=d.get("ideal_bytes_per_axis"),
                   tolerance=d.get("tolerance"),
                   max_programs=d.get("max_programs"))


# ---------------------------------------------------------------------------
# HLO parsing: collectives, replica groups -> mesh axes, aliasing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?P<shape>\([^=]*?\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?P<async>-start)?\(")
_ARRAY_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,<=\s]*)\]")
_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_GROUP_RE = re.compile(r"\{([\d,\s]*)\}")


def _braced_attr(line, attr):
    """The balanced ``{...}`` payload of ``attr={...}`` in an HLO line
    (replica_groups / source_target_pairs hold NESTED braces, so a
    non-greedy regex would truncate at the first close)."""
    marker = attr + "={"
    start = line.find(marker)
    if start < 0:
        return None
    seg = line[start + len(marker):]
    depth = 1
    for i, ch in enumerate(seg):
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                return seg[:i]
    return None
_ALIAS_ENTRY_RE = re.compile(
    r"\(\s*\d+\s*,\s*\{[^}]*\}\s*(?:,\s*(?:may|must)-alias\s*)?\)")


def _shape_bytes(spec):
    """Total bytes of an HLO result shape spec — ``f32[4,8]{1,0}`` or a
    tuple ``(f32[16]{0}, f32[16]{0})``. Unknown dtypes count 4."""
    total = 0
    for dtype, dims in _ARRAY_RE.findall(spec):
        n = 1
        for d in dims.split(","):
            d = d.strip().replace("<=", "")
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def _mesh_axis_groups(mesh):
    """{axis_label: frozenset of device-id groups} for every non-trivial
    subset of mesh axes. A collective's replica groups are matched
    against these partitions to name the axis (or axis combination —
    labelled ``"dp+tp"``) it spans."""
    if mesh is None:
        return {}
    names = list(mesh.axis_names)
    ids = _np.vectorize(lambda d: getattr(d, "id", d))(
        _np.asarray(mesh.devices))
    k = len(names)
    out = {}
    for bits in range(1, 2 ** k):
        subset = [i for i in range(k) if bits >> i & 1]
        if any(ids.shape[i] <= 1 for i in subset):
            continue  # size-1 axes produce degenerate duplicate labels
        rest = [i for i in range(k) if i not in subset]
        size = int(_np.prod([ids.shape[i] for i in subset], dtype=int))
        arr = ids.transpose(rest + subset).reshape(-1, size)
        groups = frozenset(frozenset(int(x) for x in row) for row in arr)
        out["+".join(names[i] for i in subset)] = groups
    return out


def _parse_groups(line):
    """Device-id groups of one collective line, or None (no groups —
    e.g. a degenerate replica_groups={})."""
    m = _IOTA_RE.search(line)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = _np.arange(int(_np.prod(dims, dtype=int))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(",")])
        rows = ids.reshape(n_groups, group_size)
        return frozenset(frozenset(int(x) for x in row) for row in rows)
    body = _braced_attr(line, "replica_groups")
    if body is not None:
        groups = [frozenset(int(x) for x in g.split(",") if x.strip())
                  for g in _GROUP_RE.findall(body)]
        groups = [g for g in groups if g]
        return frozenset(groups) if groups else None
    return None


def _axis_for_groups(groups, axis_groups):
    if groups is None:
        return "world"
    for label, expect in axis_groups.items():
        if groups == expect:
            return label
    sizes = sorted(len(g) for g in groups)
    return "unknown[%dx%d]" % (len(groups), sizes[-1] if sizes else 0)


def _axis_for_pairs(line, axis_groups):
    """collective-permute: name the smallest axis partition containing
    every source->target edge."""
    body = _braced_attr(line, "source_target_pairs")
    if body is None:
        return "world"
    pairs = [tuple(int(x) for x in g.split(",") if x.strip())
             for g in _GROUP_RE.findall(body)]
    pairs = [p for p in pairs if len(p) == 2]
    for label, groups in sorted(axis_groups.items(),
                                key=lambda kv: min(len(g) for g in kv[1])):
        if all(any(s in g and t in g for g in groups) for s, t in pairs):
            return label
    return "unknown[permute]"


def parse_hlo_collectives(hlo_text, mesh=None):
    """Ordered list of collectives in a compiled HLO module:
    ``[{"op", "axis", "bytes", "shape"}]``. ``bytes`` is the
    per-partition result-buffer size (the ZeroShardLayout convention:
    an all-reduce counts full grad bytes, an all-gather counts the
    gathered/padded output). Async ``-start``/``-done`` pairs count
    once."""
    axis_groups = _mesh_axis_groups(mesh)
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        if op == "collective-permute":
            axis = _axis_for_pairs(line, axis_groups)
        else:
            axis = _axis_for_groups(_parse_groups(line), axis_groups)
        nbytes = _shape_bytes(m.group("shape"))
        if m.group("async"):
            # the start op's tuple result carries (operand, result, ...)
            # scratch; counting it whole would double the payload
            nbytes //= 2
        out.append({"op": op, "axis": axis, "bytes": int(nbytes),
                    "shape": m.group("shape").strip()})
    return out


def _parse_realized_aliases(hlo_text):
    """Number of input/output aliases the COMPILED module realized
    (``input_output_alias={...}`` in the entry header) — the ground
    truth TPL304 compares declared donation against."""
    for line in hlo_text.splitlines():
        if "input_output_alias={" not in line:
            continue
        seg = line.split("input_output_alias={", 1)[1]
        depth, end = 1, 0
        for i, ch in enumerate(seg):
            if ch == "{":
                depth += 1
            elif ch == "}":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        return len(_ALIAS_ENTRY_RE.findall(seg[:end]))
    return 0


# ---------------------------------------------------------------------------
# contract extraction
# ---------------------------------------------------------------------------

def family_stats(builder):
    """{"programs", "weak_type_splits"} over a builder's compiled keys —
    the TPL303 input. A split is a group of keys identical after erasing
    weak_type and explicit-sharding decorations: distinct executables
    for what callers think is one program."""
    keys = builder.program_keys()
    base = {}
    for treedef, sigs in keys:
        erased = (str(treedef),
                  tuple((tuple(s[0]), str(s[1])) for s in sigs))
        base.setdefault(erased, 0)
        base[erased] += 1
    return {"programs": len(keys),
            "weak_type_splits": sum(1 for n in base.values() if n > 1)}


def extract_contract(builder, args, mesh=None, plan=None, site=None):
    """The audited contract of ONE ProgramBuilder entry.

    Reuses the builder's cached trace/lowering/executable
    (``lowered()``/``aot()``) — the audit never traces a throwaway twin
    of the program it inspects (ISSUE 20 satellite; asserted via
    ``builder.traces`` in the tests)."""
    args = tuple(args)
    lowered = builder.lowered(*args)
    exe = builder.aot(*args)
    hlo = exe.as_text()
    colls = parse_hlo_collectives(hlo, mesh)

    agg, order = {}, []
    per_axis = {}
    for c in colls:
        key = (c["op"], c["axis"])
        if key not in agg:
            agg[key] = {"op": c["op"], "axis": c["axis"], "count": 0,
                        "bytes": 0}
            order.append(key)
        agg[key]["count"] += 1
        agg[key]["bytes"] += c["bytes"]
        per_axis[c["axis"]] = per_axis.get(c["axis"], 0) + c["bytes"]

    ca = lowered.cost_analysis() or {}
    ma = exe.memory_analysis()
    arg_b = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
    out_b = int(getattr(ma, "output_size_in_bytes", 0) or 0)
    tmp_b = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
    peak = int(getattr(ma, "peak_memory_in_bytes", 0) or 0)
    if not peak:
        # backends without a peak stat (host CPU): the documented
        # fallback is the live-set upper bound arg+out+temp
        peak = arg_b + out_b + tmp_b

    fam = family_stats(builder)
    donate = tuple(builder.stats().get("donate_argnums", ()))
    contract = {
        "site": site or builder.site,
        "mesh_axes": ({str(a): int(mesh.shape[a]) for a in mesh.axis_names}
                      if mesh is not None else None),
        "collective_seq": ["%s@%s" % (c["op"], c["axis"]) for c in colls],
        "collectives": [agg[k] for k in order],
        "comm_bytes_per_axis": per_axis,
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "argument_bytes": arg_b,
        "output_bytes": out_b,
        "temp_bytes": tmp_b,
        "peak_bytes": peak,
        "donation": {"declared": len(donate),
                     "realized": _parse_realized_aliases(hlo)},
        "programs": fam["programs"],
        "weak_type_splits": fam["weak_type_splits"],
    }
    return contract


# ---------------------------------------------------------------------------
# audits: contract vs declared plan, contract vs committed manifest
# ---------------------------------------------------------------------------

def audit_contract(contract, plan, where=None, tolerance=None):
    """TPL3xx findings of one live contract against its DECLARED plan
    (no manifest involved): stray collectives (TPL301), drift vs the
    analytic ideal (TPL302), family explosion (TPL303)."""
    if plan is None:
        return []
    where = where or "<audit:%s>" % contract["site"]
    tol = tolerance if tolerance is not None else (
        plan.tolerance if plan.tolerance is not None else DEFAULT_TOLERANCE)
    findings = []
    for c in contract["collectives"]:
        cap = plan.allows(c["op"], c["axis"])
        if cap is None:
            findings.append(_finding(
                "TPL301",
                "stray collective: %dx %s over axis '%s' (%d bytes) not in "
                "the declared comm plan for %s (allowed: %s)"
                % (c["count"], c["op"], c["axis"], c["bytes"],
                   contract["site"],
                   sorted(set("%s@%s" % (a, x)
                              for a, x, _ in plan.allowed)) or "none"),
                where))
        elif c["count"] > cap:
            findings.append(_finding(
                "TPL301",
                "collective count exceeds plan: %dx %s over axis '%s' "
                "(plan caps it at %d) in %s"
                % (c["count"], c["op"], c["axis"], cap, contract["site"]),
                where))
    for axis, ideal in (plan.ideal_bytes_per_axis or {}).items():
        live = contract["comm_bytes_per_axis"].get(axis, 0)
        if ideal > 0 and abs(live - ideal) > tol * ideal:
            findings.append(_finding(
                "TPL302",
                "comm bytes over axis '%s' drifted vs the analytic ideal: "
                "live %d vs ideal %d (%.1f%%, tolerance %.0f%%) in %s"
                % (axis, live, ideal, 100.0 * (live - ideal) / ideal,
                   100.0 * tol, contract["site"]),
                where))
    if plan.max_programs is not None \
            and contract["programs"] > plan.max_programs:
        findings.append(_finding(
            "TPL303",
            "program family of %s holds %d executables but the plan "
            "declares at most %d" % (contract["site"],
                                     contract["programs"],
                                     plan.max_programs), where))
    if contract["weak_type_splits"]:
        findings.append(_finding(
            "TPL303",
            "%d weak_type/layout-split program group(s) at %s: the same "
            "shapes compiled more than once (silent cache bloat — "
            "normalize scalar dtypes at the call site)"
            % (contract["weak_type_splits"], contract["site"]), where))
    return findings


def diff_contract(live, manifest, where=None, tolerance=DEFAULT_TOLERANCE):
    """TPL3xx findings of a live contract against its COMMITTED manifest
    contract — the sanitizer-baseline diff the CI stage gates on.
    Regressions fail; improvements print as info-severity drift so the
    manifest gets re-pinned deliberately."""
    where = where or "<audit:%s>" % live["site"]
    tol = tolerance if tolerance is not None else DEFAULT_TOLERANCE
    findings = []
    man_coll = {(c["op"], c["axis"]): c for c in manifest.get("collectives",
                                                              ())}
    for c in live["collectives"]:
        pinned = man_coll.get((c["op"], c["axis"]))
        if pinned is None:
            findings.append(_finding(
                "TPL301",
                "collective not in the committed manifest: %dx %s over "
                "axis '%s' (%d bytes) appeared in %s"
                % (c["count"], c["op"], c["axis"], c["bytes"],
                   live["site"]), where))
        elif c["count"] > pinned["count"]:
            findings.append(_finding(
                "TPL301",
                "collective count grew vs manifest: %dx %s over axis "
                "'%s' (manifest pins %d) in %s"
                % (c["count"], c["op"], c["axis"], pinned["count"],
                   live["site"]), where))
    for axis, man_b in manifest.get("comm_bytes_per_axis", {}).items():
        live_b = live["comm_bytes_per_axis"].get(axis, 0)
        if man_b > 0 and abs(live_b - man_b) > tol * man_b:
            findings.append(_finding(
                "TPL302",
                "comm bytes over axis '%s' drifted vs manifest: live %d "
                "vs pinned %d (%.1f%%, tolerance %.0f%%) in %s"
                % (axis, live_b, man_b,
                   100.0 * (live_b - man_b) / man_b, 100.0 * tol,
                   live["site"]), where))
    for axis, live_b in live["comm_bytes_per_axis"].items():
        if axis not in manifest.get("comm_bytes_per_axis", {}) and live_b:
            findings.append(_finding(
                "TPL302",
                "comm bytes appeared on axis '%s' (%d bytes) with no "
                "manifest entry in %s" % (axis, live_b, live["site"]),
                where))
    if live["programs"] > manifest.get("programs", live["programs"]):
        findings.append(_finding(
            "TPL303",
            "program family grew vs manifest: %d executables at %s "
            "(manifest pins %d)" % (live["programs"], live["site"],
                                    manifest["programs"]), where))
    if live["weak_type_splits"] > manifest.get("weak_type_splits", 0):
        findings.append(_finding(
            "TPL303",
            "%d weak_type/layout-split group(s) at %s (manifest pins %d)"
            % (live["weak_type_splits"], live["site"],
               manifest.get("weak_type_splits", 0)), where))
    man_peak = manifest.get("peak_bytes", 0)
    if man_peak and live["peak_bytes"] > (1.0 + tol) * man_peak:
        findings.append(_finding(
            "TPL304",
            "peak memory regressed vs manifest: %d bytes vs pinned %d "
            "(+%.1f%%, tolerance %.0f%%) in %s"
            % (live["peak_bytes"], man_peak,
               100.0 * (live["peak_bytes"] - man_peak) / man_peak,
               100.0 * tol, live["site"]), where))
    man_don = manifest.get("donation", {})
    if live["donation"]["realized"] < man_don.get("realized", 0):
        findings.append(_finding(
            "TPL304",
            "donation regression: %d of %d declared donated args realized "
            "as aliases in %s (manifest pins %d) — a donated buffer the "
            "compiled program no longer reuses"
            % (live["donation"]["realized"], live["donation"]["declared"],
               live["site"], man_don.get("realized", 0)), where))
    return findings


def _apply_manifest_allows(findings, allows, where):
    """Manifest-carried suppressions — the pragma contract
    (findings.apply_pragmas) transplanted to JSON: slug match suppresses,
    a missing reason suppresses NOTHING and raises TPL000."""
    extra = []
    for ent in allows or ():
        slug = ent.get("slug", "")
        reason = (ent.get("reason") or "").strip()
        if not reason:
            extra.append(Finding(
                "TPL000", "pragma", Severity.ERROR,
                "manifest allow-entry %r has no reason; a bare entry "
                "suppresses nothing" % slug, where))
            continue
        for f in findings:
            if f.slug == slug and not f.suppressed:
                f.suppressed = True
                f.suppress_reason = reason
    return extra


# ---------------------------------------------------------------------------
# manifests
# ---------------------------------------------------------------------------

def manifest_path(name, directory=None):
    return os.path.join(manifest_dir(directory), "%s.json" % name)


def load_manifest(name, directory=None):
    path = manifest_path(name, directory)
    if not os.path.isfile(path):
        from ..base import MXNetError
        raise MXNetError(
            "program manifest %s is missing — run `python -m "
            "mxnet_tpu.analysis.lint --audit --update-manifests` and "
            "commit ci/program_manifests/" % path)
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_manifest(name, units, directory=None):
    """Write one program's manifest: {unit: {contract..., "plan": ...}}.
    Existing ``allow`` suppression entries survive the rewrite (they are
    reviewer-owned, like pragmas)."""
    path = manifest_path(name, directory)
    old_units = {}
    if os.path.isfile(path):
        with open(path, encoding="utf-8") as f:
            old_units = json.load(f).get("units", {})
    doc = {"program": name, "format": 1, "units": {}}
    for unit, (contract, plan) in units.items():
        entry = dict(contract)
        if plan is not None:
            entry["plan"] = plan.as_dict()
        allow = old_units.get(unit, {}).get("allow")
        if allow:
            entry["allow"] = allow
        doc["units"][unit] = entry
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


# ---------------------------------------------------------------------------
# the core program fixtures (one manifest each)
# ---------------------------------------------------------------------------

class AuditUnit:
    """One auditable program: a builder + the abstract args selecting the
    program, the mesh its collectives partition over, and its plan."""

    __slots__ = ("name", "builder", "args", "mesh", "plan")

    def __init__(self, name, builder, args, mesh=None, plan=None):
        self.name = name
        self.builder = builder
        self.args = tuple(args)
        self.mesh = mesh
        self.plan = plan


def reference_mesh(dp=4, tp=2):
    """The 4x2 (dp, tp) reference mesh every manifest is pinned on.
    Needs >= dp*tp host devices (ci/envutil.cpu_mesh_env arranges 8)."""
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    need = dp * tp
    if len(devs) < need:
        from ..base import MXNetError
        raise MXNetError(
            "program audit needs %d devices but found %d — run under "
            "ci/envutil.cpu_mesh_env(%d) (XLA_FLAGS="
            "--xla_force_host_platform_device_count=%d before jax loads)"
            % (need, len(devs), need, need))
    return Mesh(_np.asarray(devs[:need]).reshape(dp, tp), ("dp", "tp"))


def _mlp_symbol():
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=8, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _build_executor_fwd():
    import jax
    import mxnet_tpu as mx
    from ..context import cpu
    from ..executor import Executor
    from ..ndarray.ndarray import zeros as nd_zeros
    from .. import random as _rnd
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(act, num_hidden=8, name="fc2")
    arg_shapes, _, aux_shapes = net.infer_shape(data=(8, 12))
    args = {n: nd_zeros(s) for n, s in zip(net.list_arguments(),
                                           arg_shapes)}
    aux = {n: nd_zeros(s) for n, s in zip(net.list_auxiliary_states(),
                                          aux_shapes)}
    ex = Executor(net, cpu(), args, {}, "null", aux)
    arg_sds = {n: jax.ShapeDtypeStruct(a.shape, a._data.dtype)
               for n, a in ex.arg_dict.items()}
    aux_sds = {n: jax.ShapeDtypeStruct(a.shape, a._data.dtype)
               for n, a in ex.aux_dict.items()}
    rng = _rnd.fixed_key()
    rng_sds = jax.ShapeDtypeStruct(rng.shape, rng.dtype)
    plan = CommPlan(site="executor.forward", allowed=(), max_programs=1)
    return [AuditUnit("forward", ex._fwd_fn(False),
                      (arg_sds, aux_sds, rng_sds), plan=plan)]


def _train_step(mesh, zero):
    from ..parallel.tpu_step import DataParallelTrainStep
    step = DataParallelTrainStep(
        _mlp_symbol(), mesh, lr=0.1, momentum=0.9,
        data_names=("data",), label_names=("softmax_label",),
        zero=zero, shard_update=None if zero else True,
        fused_optupdate=False)
    step.init({"data": (16, 12), "softmax_label": (16,)})
    return AuditUnit("step", step._step, step.abstract_step_args(),
                     mesh=mesh, plan=step.comm_plan())


def _build_fused_step():
    return [_train_step(reference_mesh(), zero=False)]


def _build_zero_step():
    return [_train_step(reference_mesh(), zero=True)]


def _build_mesh_kernels():
    import jax
    from ..compile.builder import ProgramBuilder
    from ..parallel.mesh_kernels import (flash_attention_mesh,
                                         flash_mesh_comm_plan,
                                         fused_update_mesh,
                                         optupdate_mesh_comm_plan)
    mesh = reference_mesh()
    f32 = _np.float32

    # flash island: dp x tp sharded, ZERO collectives — a meaningful
    # empty plan (anything appearing here is partitioner-injected).
    # Tier pinned to lax so the manifest is env-independent.
    def flash(q, k, v):
        return flash_attention_mesh(q, k, v, mesh, use_pallas=False,
                                    interpret=False)

    qsd = jax.ShapeDtypeStruct((4, 2, 128, 32), f32)
    flash_b = ProgramBuilder(flash, site="mesh.flash_attention")
    units = [AuditUnit("flash_attention", flash_b, (qsd, qsd, qsd),
                       mesh=mesh,
                       plan=flash_mesh_comm_plan(mesh))]

    # fused optimizer update island: all-gather over dp (params + slots
    # regather from their transient (dp, chunk) blocks)
    params = {"w": jax.ShapeDtypeStruct((16, 16), f32),
              "b": jax.ShapeDtypeStruct((16,), f32)}

    def upd(p, mom, g):
        return fused_update_mesh("sgd", {"lr": 0.1, "momentum": 0.9},
                                 p, {"mom": mom}, g, mesh, "dp",
                                 use_pallas=False, interpret=False)

    upd_b = ProgramBuilder(upd, site="mesh.fused_update")
    units.append(AuditUnit(
        "fused_update", upd_b, (params, dict(params), dict(params)),
        mesh=mesh,
        plan=optupdate_mesh_comm_plan("sgd", params, mesh, "dp",
                                      opt_state={"mom": params})))
    return units


def _build_serving_buckets():
    import jax
    import jax.numpy as jnp
    from ..serving.program_cache import BucketedProgramCache

    def fn(batch, params, aux, rng):
        return (jnp.tanh(batch["x"] @ params["w"]),)

    cache = BucketedProgramCache(fn, buckets=(1, 4), donate=False,
                                 site="serving.audit")
    template = {"x": _np.ones((2, 8), _np.float32)}
    params = {"w": _np.ones((8, 4), _np.float32)}
    rng = jax.random.PRNGKey(0)
    cache.warmup(template, params, {}, rng)
    sd = jax.ShapeDtypeStruct
    args = ({"x": sd((4, 8), _np.float32)},
            {"w": sd((8, 4), _np.float32)}, {},
            sd(tuple(rng.shape), rng.dtype))
    return [AuditUnit("bucket4", cache._builder, args,
                      plan=cache.comm_plan())]


def _build_decode():
    import jax
    from ..models.tiny_lm import TinyLMDecodeModel
    from ..serving.decode import DecodeEngine
    eng = DecodeEngine(**TinyLMDecodeModel().engine_kwargs(),
                       name="audit", num_blocks=32,
                       batch_size=2, max_seq_len=32, prefill_buckets=(8,),
                       prefill_chunk=0, warmup=True, autostart=False)
    sd = jax.ShapeDtypeStruct
    i32 = _np.int32
    cache = eng._cache_spec
    params = jax.tree_util.tree_map(
        lambda x: sd(tuple(x.shape), x.dtype), eng._params)
    mb = eng._mb
    plans = eng.comm_plan()
    prefill_args = (params, cache, sd((8,), i32), sd((), i32),
                    sd((), i32), sd((mb,), i32), sd((), i32))
    b = eng.batch_size
    step_args = (params, cache, sd((b,), i32), sd((b,), i32),
                 sd((b, mb), i32), sd((b,), _np.bool_))
    return [AuditUnit("prefill", eng._prefill_b, prefill_args,
                      plan=plans["prefill"]),
            AuditUnit("step", eng._step_b, step_args, plan=plans["step"])]


CORE_PROGRAMS = ("executor_fwd", "fused_step", "zero_step", "mesh_kernels",
                 "serving_buckets", "decode")

_BUILDERS = {
    "executor_fwd": _build_executor_fwd,
    "fused_step": _build_fused_step,
    "zero_step": _build_zero_step,
    "mesh_kernels": _build_mesh_kernels,
    "serving_buckets": _build_serving_buckets,
    "decode": _build_decode,
}


def build_mispinned_zero_unit(mesh=None, mispin=True):
    """The PR 7 regression twin: the REAL ZeRO update island
    (optim_update.apply_update_sharded) built through ProgramBuilder,
    with the grads' jit-level sharding deliberately mis-pinned over the
    'tp' axis. The island wants replicated grads, so GSPMD inserts an
    all-gather over tp — a stray collective the declared (dp-only) plan
    rejects: TPL301 names the op and the axis. ``mispin=False`` builds
    the correctly-pinned control, which audits green."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..compile.builder import ProgramBuilder
    from ..parallel.optim_update import apply_update_sharded, init_opt_state
    from ..parallel.zero import ZeroShardLayout
    mesh = mesh or reference_mesh()
    dp = int(mesh.shape["dp"])
    params = {"w": _np.zeros((16, 16), _np.float32),
              "b": _np.zeros((16,), _np.float32)}
    layout = ZeroShardLayout.from_params(params, dp, axis_name="dp")
    state = init_opt_state("sgd", params, momentum=0.9, layout=layout)

    def stepfn(p, s, g, lr):
        return apply_update_sharded("sgd", {"lr": lr, "momentum": 0.9},
                                    p, s, g, layout, mesh)

    repl = NamedSharding(mesh, P())
    grad_sh = NamedSharding(mesh, P("tp")) if mispin else repl
    zsh = layout.sharding(mesh)
    in_shardings = ({n: repl for n in params},
                    {"mom": {n: zsh for n in params}},
                    {n: grad_sh for n in params}, None)
    builder = ProgramBuilder(
        stepfn, site="train.zero_update%s" % ("_mispinned" if mispin
                                              else ""),
        in_shardings=in_shardings)
    sds = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype),
        (params, state, params, _np.float32(0.1)))
    comm = layout.comm_bytes()
    plan = CommPlan(site=builder.site,
                    allowed=[("all-gather", "dp", None),
                             ("reduce-scatter", "dp", None),
                             ("all-reduce", "dp", None)],
                    ideal_bytes_per_axis={"dp": comm["gather_bytes"]},
                    max_programs=1)
    return AuditUnit("zero_update", builder, sds, mesh=mesh, plan=plan)


# ---------------------------------------------------------------------------
# the audit driver
# ---------------------------------------------------------------------------

def run_audit(names=None, update=False, directory=None, tolerance=None):
    """Build the core program fixtures on the reference mesh, extract
    live contracts, audit them against their declared plans and diff
    them against the committed manifests (or re-pin with ``update``).

    Returns ``(findings, contracts)`` where contracts is
    ``{program: {unit: contract}}``. Findings route through the
    existing reporter (profiler.analysis_counters + the analysis
    logger) — always-on, exactly like the TPL2xx sweeps."""
    from .. import profiler
    from .runtime import report_findings
    tol = tolerance if tolerance is not None else audit_tolerance()
    findings, contracts = [], {}
    for prog in (names or CORE_PROGRAMS):
        if prog not in _BUILDERS:
            from ..base import MXNetError
            raise MXNetError("unknown audit program %r (have: %s)"
                             % (prog, ", ".join(CORE_PROGRAMS)))
        units = _BUILDERS[prog]()
        built = {}
        prog_findings = []
        for u in units:
            c = extract_contract(u.builder, u.args, mesh=u.mesh,
                                 plan=u.plan)
            built[u.name] = (c, u.plan)
            prog_findings.extend(audit_contract(
                c, u.plan, where="audit:%s/%s" % (prog, u.name),
                tolerance=tolerance))
        profiler.record_analysis_check(len(units))
        if update:
            write_manifest(prog, built, directory)
        else:
            man = load_manifest(prog, directory)
            for unit, (c, _plan) in built.items():
                entry = man.get("units", {}).get(unit)
                where = "%s:%s" % (manifest_path(prog, directory), unit)
                if entry is None:
                    prog_findings.append(_finding(
                        "TPL303",
                        "program unit %s/%s has no manifest entry — run "
                        "--update-manifests" % (prog, unit), where))
                    continue
                unit_findings = diff_contract(c, entry, where=where,
                                              tolerance=tol)
                prog_findings.extend(_apply_manifest_allows(
                    unit_findings, entry.get("allow"), where))
                prog_findings.extend(unit_findings)
        findings.extend(prog_findings)
        contracts[prog] = {k: v[0] for k, v in built.items()}
    report_findings([f for f in findings if not f.suppressed])
    return findings, contracts


# ---------------------------------------------------------------------------
# generated docs: the comm-plan table (docs/faq/comm_plans.md)
# ---------------------------------------------------------------------------

def _fmt_bytes(n):
    if n >= 1 << 20:
        return "%.1f MiB" % (n / float(1 << 20))
    if n >= 1 << 10:
        return "%.1f KiB" % (n / float(1 << 10))
    return "%d B" % n


def emit_comm_plans_doc(directory=None, out_path=None):
    """Regenerate docs/faq/comm_plans.md from the committed manifests —
    the declared comm plans doubling as documentation (program ->
    collectives -> bytes/axis on the 4x2 reference mesh)."""
    directory = manifest_dir(directory)
    out_path = out_path or os.path.join(_REPO_ROOT, "docs", "faq",
                                        "comm_plans.md")
    lines = [
        "# Program comm plans (generated)",
        "",
        "Generated by `python -m mxnet_tpu.analysis.lint --audit "
        "--update-manifests` from the committed program manifests "
        "(`ci/program_manifests/*.json`) — do not edit by hand.",
        "",
        "Every core compiled program's collective contract on the 4x2 "
        "`(dp=4, tp=2)` reference mesh, as audited by the TPL3xx passes "
        "(`docs/faq/analysis.md`). *Bytes* are per-partition "
        "result-buffer bytes, the same convention as the ZeRO byte "
        "accounting and the mesh-kernel rooflines "
        "(`docs/faq/perf.md`).",
        "",
        "| program | unit | collectives | comm bytes / axis | peak bytes "
        "| programs |",
        "|---|---|---|---|---|---|",
    ]
    for prog in CORE_PROGRAMS:
        path = manifest_path(prog, directory)
        if not os.path.isfile(path):
            continue
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        for unit in sorted(doc.get("units", {})):
            c = doc["units"][unit]
            colls = ", ".join(
                "%dx %s@%s" % (e["count"], e["op"], e["axis"])
                for e in c.get("collectives", ())) or "none"
            bytes_axis = ", ".join(
                "%s: %s" % (a, _fmt_bytes(b))
                for a, b in sorted(c.get("comm_bytes_per_axis",
                                         {}).items())) or "0"
            lines.append("| %s | %s | %s | %s | %s | %d |" % (
                prog, unit, colls, bytes_axis,
                _fmt_bytes(c.get("peak_bytes", 0)), c.get("programs", 0)))
    lines += [
        "",
        "A collective beyond this table fails CI with TPL301 "
        "(stray-collective); per-axis byte drift beyond tolerance fails "
        "with TPL302. See the \"Program contracts\" section of "
        "`docs/faq/analysis.md`.",
        "",
    ]
    with open(out_path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines))
    return out_path
