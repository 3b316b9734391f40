"""Device time by ``jax.named_scope``, from the raw ``.xplane.pb``.

An ``XLA Ops`` event of a TPU trace is named by its HLO text, which carries
no metadata; the framework's name of the operation (``jit(step)/jit(main)/
mla.attend/dot_general:``, the path the program's named scopes write) is the
``tf_op`` stat of the event's METADATA, which ``jax.profiler.ProfileData``
does not hand out.  So this parses the file with the generated
``xplane_pb2`` that the installed TensorFlow ships (loaded from its file:
importing ``tensorflow`` itself takes 15 s and 4,900 modules), on the first
device plane only.  Where that module is not installed the readers say so
once and give ``None``.

:func:`scope_seconds` gives, for the executions of the programs whose name
matches, the seconds in which an operation under each scope ran (a union of
intervals, so an operation inside a ``while`` is not counted twice).  A
trace with no device plane, or a program whose operations carry no such
scope (the parent of the PR that added them), gives ``None`` / zeros.
"""
import importlib.util
import json
import os
import re

from . import trace_reduce
from .trace_reduce import _union


def _xplane_pb2():
    """The generated module of ``tsl/profiler/protobuf/xplane.proto``, or
    ``None`` where no TensorFlow is installed."""
    spec = importlib.util.find_spec("tensorflow")
    for root in (spec.submodule_search_locations or ()) if spec else ():
        path = os.path.join(root, "tsl", "profiler", "protobuf",
                            "xplane_pb2.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module
    print("xplane_scopes: no generated xplane_pb2 is installed; the shares "
          "by named scope are not read", flush=True)
    return None


def device_ops(path):
    """``(modules, ops)`` of the first ``/device:TPU:`` plane: ``modules``
    ``[(name, start_s, end_s)]`` and ``ops`` ``[(framework op name, HLO
    name, start_s, end_s)]``, or ``None`` where the trace has no such plane
    (or the parser is not installed)."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        md_op = {}
        for key, md in plane.event_metadata.items():
            for stat in md.stats:
                if stat_names.get(stat.metadata_id) != "tf_op":
                    continue
                md_op[key] = stat.str_value if stat.HasField("str_value") \
                    else stat_names.get(stat.ref_value, "")
        modules, ops = [], []
        for line in plane.lines:
            if line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for ev in line.events:
                start = line.timestamp_ns / 1e9 + ev.offset_ps / 1e12
                end = start + ev.duration_ps / 1e12
                name = plane.event_metadata[ev.metadata_id].name
                if line.name == "XLA Modules":
                    modules.append((name, start, end))
                else:
                    ops.append((md_op.get(ev.metadata_id, ""), name, start,
                                end))
        return modules, ops
    return None


# The chip's compiler expands ``lax.ragged_dot`` into kernels of its own and
# names them itself (``ragged-dot-none``, ``ragged-dot-metadata``), dropping
# the scope they were issued under.  Only the routed experts' grouped
# products issue one.  Should the compiler rename them, the seconds turn up
# by their new name in ``step_by_scope``'s ``_unscoped`` list.
ALIASES = (("ragged-dot", "moe.experts"),)


def scope_seconds(path, module_pattern, scopes, aliases=ALIASES):
    """``{scope: seconds}`` inside the executions of the programs whose
    name matches ``module_pattern``, plus ``"_programs"`` (their seconds),
    ``"_named"`` (operations that carried any framework name) and
    ``"_unscoped"`` (the five largest ``[name, seconds]`` among the
    operations that fell under no scope, by the last component of their
    framework name or else their HLO name without its number).  An
    operation belongs to the first of ``scopes`` that is a component of its
    framework name, or that ``aliases`` gives for a prefix of its last
    component."""
    found = device_ops(path)
    if found is None:
        return None
    modules, ops = found
    reg = re.compile(module_pattern)
    runs = sorted((s, e) for name, s, e in modules if reg.search(name))
    if not runs:
        return None
    by_scope = {s: [] for s in scopes}
    unscoped = {}
    named = 0
    j = 0
    for op, hlo, s, e in sorted(ops, key=lambda x: x[2]):
        while j < len(runs) and runs[j][1] <= s:
            j += 1
        if j == len(runs) or s < runs[j][0]:
            continue
        named += bool(op)
        parts = op.rstrip(":").split("/")
        scope = next((to for prefix, to in aliases
                      if parts[-1].startswith(prefix) and to in by_scope),
                     None) or next((sc for sc in scopes if sc in parts), None)
        if scope is not None:
            by_scope[scope].append((s, e))
        else:
            key = parts[-1] or re.sub(r"[.\d]+$", "",
                                      hlo.lstrip("%").split(" ")[0])
            unscoped[key] = unscoped.get(key, 0.0) + (e - s)
    out = {scope: _union(iv)[0] for scope, iv in by_scope.items()}
    out["_programs"] = sum(e - s for s, e in runs)
    out["_named"] = named
    out["_unscoped"] = sorted(unscoped.items(), key=lambda kv: -kv[1])[:5]
    return out


ALL_SCOPES = ("mla.attend", "moe.route", "moe.experts", "moe.shared",
              "ffn.dense", "head")


def share_of_programs(obs, spec):
    """For a per-layer metric: percent of the device seconds of the
    programs ``spec["step_module"]`` spent under ``spec["scopes"]``.  The
    run's trace is read once (kept in ``obs``) and every scope's seconds
    are printed once as ``step_by_scope``.  ``None`` where there is no
    trace, no such program, or no operation under any of the scopes (a
    program that has no such named scopes)."""
    key = "step_by_scope:" + spec["step_module"]
    if key not in obs:
        found = None
        if obs.get("trace") is not None:
            root = os.path.join(obs["cell"].root, ".perf_out", "trace")
            found = scope_seconds(trace_reduce.find_xplane(root),
                                  spec["step_module"], ALL_SCOPES)
        if found is not None:
            print("step_by_scope " + json.dumps(
                {k: [[n, round(v, 6)] for n, v in found[k]]
                 if k == "_unscoped" else round(found[k], 6)
                 for k in found}), flush=True)
        obs[key] = found
    found = obs[key]
    if not found or not found["_programs"]:
        return None
    under = sum(found[s] for s in spec["scopes"])
    if not any(found[s] for s in ALL_SCOPES):
        return None
    return 100.0 * under / found["_programs"]
