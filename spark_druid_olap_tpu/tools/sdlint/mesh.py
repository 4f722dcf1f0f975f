"""SPMD mesh-safety pass.

The sharded execution tier (``parallel/mesh.py``, ``parallel/
multihost.py``, and every ``shard_map`` site in the executor) runs ONE
traced program replicated across chips; the only cross-replica
communication is the collective calls inside it. Four properties keep
that replication safe, and all four are checkable without a mesh:

- **unknown-axis-name** — a collective's axis (and the axis names in
  ``P(...)`` specs at ``shard_map`` sites) must be an axis the mesh
  module actually declares (``SEGMENT_AXIS``/``Mesh`` construction).
  A typo'd axis string fails only when the sharded path finally runs —
  which, on CPU CI, is never. Names threaded through parameters are
  accepted (the binding site is checked instead).
- **sketch-merge-mismatch** — register-valued aggregates merge by
  *register algebra*, not addition: HLL rho registers are maxima,
  theta k-min registers are minima. The expected operator is declared
  per sketch in ``ops/agg_registry.py:AGG_CLOSURE`` (``merge`` field);
  ``ops/<sketch>.py:merge_registers`` must use the matching collective
  — a ``psum`` over HLL/theta registers double-counts silently.
- **merge-op-mismatch** — in any branch dispatching on an aggregate
  ``kind == "min"``/``"max"``, the collective used must be
  ``pmin``/``pmax``; a ``psum`` there sums extrema across chips.
- **host-call-in-shard** / **host-state-write-in-shard** — code
  reachable from a ``shard_map`` body must not call host callbacks
  (``io_callback``/``pure_callback``/``jax.debug.*``), draw from
  ``jax.random`` (replicas would diverge unless keys are split per
  axis index — thread keys in explicitly), or write host-global state
  (``self.*`` attributes, module-level caches/registries/stats dicts —
  the same write vocabulary the locks pass checks): the body traces
  ONCE, so the write happens at trace time on every host, not per
  shard, and the replicas' view of it diverges from the host's.

Shard bodies are discovered exactly like the purity pass discovers
traced roots: direct ``shard_map(fn, ...)`` sites (any spelling whose
last segment is ``shard_map`` — ``jax.shard_map``, the repo's
``parallel.mesh.shard_map`` alias, lambdas), plus wrapper
functions that pass one of their own parameters into a shard_map call
(``QueryEngine._shard_wrap``), whose call-site arguments then root.
Anchors resolve by path suffix; a missing anchor skips its checks.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from spark_druid_olap_tpu.tools.sdlint.astutil import (FuncId, call_chain,
                                                       dotted_name,
                                                       resolve_kernel_refs,
                                                       walk_shallow)
from spark_druid_olap_tpu.tools.sdlint.core import Finding, Module, Project

_MESH_SUFFIX = "parallel/mesh.py"
_REGISTRY_SUFFIX = "ops/agg_registry.py"

_COLLECTIVES = frozenset({"psum", "pmin", "pmax", "pmean", "all_gather",
                          "all_to_all", "ppermute", "psum_scatter",
                          "axis_index"})
#: collectives that MERGE values (the ones a wrong operator corrupts)
_MERGE_COLLECTIVES = frozenset({"psum", "pmin", "pmax", "pmean"})
#: register algebra per sketch when the registry predates the
#: ``merge`` field; the registry declaration wins when present
_SKETCH_MERGE_DEFAULT = {"hll": "max", "theta": "min", "kll": "minsum"}
#: merge algebra -> the collective(s) it may lower to. Composite
#: algebras (KLL "minsum": lex-min survivor lanes via pmin + exact
#: level counts via psum) legitimately use more than one collective in
#: the same merge body.
_MERGE_TO_COLLECTIVE = {"sum": {"psum"}, "max": {"pmax"},
                        "min": {"pmin"}, "minsum": {"pmin", "psum"}}

#: host-callback / RNG vocabulary the purity pass does NOT already
#: flag (purity covers time/random/np.random/threading/os/...; these
#: are the jax-native escapes that only matter under replication)
_HOST_CALL_PREFIXES = ("jax.debug.", "jax.experimental.host_callback",
                       "host_callback.", "hcb.", "jax.random.",
                       "jrandom.")
_HOST_CALL_LEAVES = frozenset({"io_callback", "pure_callback",
                               "debug_callback"})

# same container-mutation vocabulary as locks._MUTATORS
_MUTATORS = frozenset({"append", "add", "update", "pop", "popitem",
                       "clear", "discard", "remove", "extend", "insert",
                       "setdefault", "appendleft"})


def _registry(mod: Module) -> Optional[Dict[str, dict]]:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "AGG_CLOSURE":
            try:
                v = ast.literal_eval(node.value)
            except ValueError:
                return None
            return v if isinstance(v, dict) else None
    return None


def _declared_axes(mod: Module) -> Dict[str, str]:
    """Axis constants the mesh module declares: ``NAME = "axis"``
    top-level string assignments plus literal axis tuples in
    ``Mesh(..., ("axis", ...))`` constructions."""
    out: Dict[str, str] = {}
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            out[node.targets[0].id] = node.value.value
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) \
                and call_chain(node.func)[-1:] == ["Mesh"] \
                and len(node.args) >= 2 \
                and isinstance(node.args[1], (ast.Tuple, ast.List)):
            for i, e in enumerate(node.args[1].elts):
                if isinstance(e, ast.Constant) \
                        and isinstance(e.value, str):
                    out.setdefault(f"<mesh-axis-{i}>", e.value)
    return out


class _Mesh:
    def __init__(self, project: Project):
        self.project = project
        self.index = project.index()
        mesh_mod = project.by_suffix(_MESH_SUFFIX)
        self.axis_consts = _declared_axes(mesh_mod) \
            if mesh_mod is not None else {}
        self.declared = set(self.axis_consts.values())
        # module name -> top-level assigned names (host-global state)
        self.module_globals: Dict[str, Set[str]] = {}
        for name, mi in self.index.modules.items():
            tops: Set[str] = set()
            for node in mi.mod.tree.body:
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            tops.add(t.id)
                elif isinstance(node, ast.AnnAssign) \
                        and isinstance(node.target, ast.Name):
                    tops.add(node.target.id)
            self.module_globals[name] = tops
        self.wrapper_params: Dict[FuncId, Set[str]] = {}
        self._find_wrapper_params()
        self.roots: Dict[FuncId, Tuple[str, int]] = {}
        self._find_roots()
        self.reachable = self._reach()

    # -- shard-body discovery (mirrors purity's root discovery) ---------------
    def _find_wrapper_params(self) -> None:
        for fid, fn in self.index.functions.items():
            params = {a.arg for a in fn.args.args}
            aliases: Dict[str, str] = {}
            traced: Set[str] = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id in params:
                    aliases[node.targets[0].id] = node.value.id
                if isinstance(node, ast.Call) and node.args \
                        and isinstance(node.func, (ast.Name,
                                                   ast.Attribute)) \
                        and call_chain(node.func)[-1:] == ["shard_map"]:
                    a = node.args[0]
                    if isinstance(a, ast.Name):
                        p = a.id if a.id in params else aliases.get(a.id)
                        if p:
                            traced.add(p)
            if traced:
                self.wrapper_params[fid] = traced

    def _add_root(self, mi, ci, expr: ast.expr, local,
                  enclosing_qual: str, site: Tuple[str, int]) -> None:
        idx = self.index
        if isinstance(expr, ast.Lambda):
            for node in ast.walk(expr.body):
                if isinstance(node, ast.Call):
                    for callee in idx.resolve_call(
                            mi, ci, node, local,
                            enclosing_qual=enclosing_qual):
                        self.roots.setdefault(callee, site)
            return
        for ref in resolve_kernel_refs(idx, mi, ci, expr, local,
                                       enclosing_qual=enclosing_qual):
            self.roots.setdefault(ref, site)

    def _find_roots(self) -> None:
        idx = self.index
        for fid, fn in idx.functions.items():
            mi = idx.modules[fid[0]]
            ci = idx.func_class[fid]
            local = idx.local_types(mi, ci, fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, (ast.Name, ast.Attribute)) \
                        and call_chain(node.func)[-1:] == ["shard_map"] \
                        and node.args:
                    # skip the compat wrapper's own body (it forwards
                    # its parameter; the real bodies root at call sites)
                    self._add_root(mi, ci, node.args[0], local, fid[1],
                                   (mi.mod.relpath, node.lineno))
                    continue
                for callee in idx.resolve_call(mi, ci, node, local,
                                               enclosing_qual=fid[1],
                                               unique_fallback=True):
                    traced = self.wrapper_params.get(callee)
                    if not traced:
                        continue
                    cfn = idx.functions[callee]
                    pnames = [a.arg for a in cfn.args.args]
                    if pnames and pnames[0] == "self":
                        pnames = pnames[1:]
                    for i, a in enumerate(node.args):
                        if i < len(pnames) and pnames[i] in traced:
                            self._add_root(mi, ci, a, local, fid[1],
                                           (mi.mod.relpath, node.lineno))
                    for kw in node.keywords:
                        if kw.arg in traced:
                            self._add_root(mi, ci, kw.value, local,
                                           fid[1],
                                           (mi.mod.relpath, node.lineno))

    def _reach(self) -> Set[FuncId]:
        idx = self.index
        seen = set(self.roots)
        stack = list(self.roots)
        while stack:
            fid = stack.pop()
            fn = idx.functions.get(fid)
            if fn is None:
                continue
            mi = idx.modules[fid[0]]
            ci = idx.func_class[fid]
            local = idx.local_types(mi, ci, fn)
            for node in walk_shallow(fn):
                if isinstance(node, ast.Call):
                    for callee in idx.resolve_call(mi, ci, node, local,
                                                   enclosing_qual=fid[1]):
                        if callee not in seen:
                            seen.add(callee)
                            stack.append(callee)
        return seen

    # -- unknown-axis-name -----------------------------------------------------
    def _axis_value(self, mi, fn: ast.FunctionDef,
                    expr: ast.expr) -> Optional[str]:
        """Statically resolvable axis value of ``expr``; None when
        unknown (parameters, computed values) — unknown is accepted."""
        if isinstance(expr, ast.Constant):
            return expr.value if isinstance(expr.value, str) else None
        if isinstance(expr, ast.Attribute):
            return self.axis_consts.get(expr.attr)
        if isinstance(expr, ast.Name):
            params = {a.arg for a in fn.args.posonlyargs + fn.args.args
                      + fn.args.kwonlyargs}
            if expr.id in params:
                return None
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and node.targets[0].id == expr.id:
                    if isinstance(node.value, ast.Constant) \
                            and isinstance(node.value.value, str):
                        return node.value.value
                    return None
            imp = mi.imports.get(expr.id)
            if imp and imp[0] == "symbol":
                return self.axis_consts.get(imp[2])
            return self.axis_consts.get(expr.id)
        return None

    def axis_findings(self) -> List[Finding]:
        if not self.declared:
            return []          # no mesh anchor: nothing to check against
        out: List[Finding] = []
        idx = self.index
        mesh_mod = self.project.by_suffix(_MESH_SUFFIX)
        for fid, fn in sorted(idx.functions.items()):
            mi = idx.modules[fid[0]]
            if mesh_mod is not None and mi.mod is mesh_mod:
                continue       # the declaration site itself
            for node in walk_shallow(fn):
                if not isinstance(node, ast.Call):
                    continue
                chain = call_chain(node.func)
                if chain and chain[-1] in _COLLECTIVES:
                    ax = self._collective_axis_arg(node, chain[-1])
                    if ax is None:
                        continue
                    val = self._axis_value(mi, fn, ax)
                    if val is not None and val not in self.declared:
                        out.append(Finding(
                            "mesh", "unknown-axis-name", mi.mod.relpath,
                            node.lineno, f"{fid[1]}:{val}",
                            f"{fid[1]} runs {chain[-1]} over axis "
                            f"{val!r} but the mesh "
                            f"({_MESH_SUFFIX}) only declares "
                            f"{sorted(self.declared)}; this fails only "
                            f"when the sharded path finally runs"))
                elif chain and chain[-1] == "shard_map":
                    out.extend(self._spec_axis_findings(fid, mi, fn,
                                                        node))
        return out

    @staticmethod
    def _collective_axis_arg(node: ast.Call,
                             leaf: str) -> Optional[ast.expr]:
        for kw in node.keywords:
            if kw.arg == "axis_name":
                return kw.value
        pos = 0 if leaf == "axis_index" else 1
        if len(node.args) > pos:
            return node.args[pos]
        return None

    def _spec_axis_findings(self, fid, mi, fn,
                            call: ast.Call) -> List[Finding]:
        out: List[Finding] = []
        spec_exprs = list(call.args[1:]) \
            + [kw.value for kw in call.keywords
               if kw.arg in ("in_specs", "out_specs")]
        for root in spec_exprs:
            for node in ast.walk(root):
                if isinstance(node, ast.Call) and call_chain(
                        node.func)[-1:] in (["P"], ["PartitionSpec"]):
                    for a in node.args:
                        val = self._axis_value(mi, fn, a)
                        if val is not None \
                                and val not in self.declared:
                            out.append(Finding(
                                "mesh", "unknown-axis-name",
                                mi.mod.relpath, node.lineno,
                                f"{fid[1]}:{val}",
                                f"{fid[1]} partitions over axis "
                                f"{val!r} in a shard_map spec but the "
                                f"mesh ({_MESH_SUFFIX}) only declares "
                                f"{sorted(self.declared)}"))
        return out

    # -- sketch-merge-mismatch -------------------------------------------------
    def sketch_findings(self) -> List[Finding]:
        reg_mod = self.project.by_suffix(_REGISTRY_SUFFIX)
        if reg_mod is None:
            return []
        registry = _registry(reg_mod)
        if not registry:
            return []
        out: List[Finding] = []
        seen_sketches: Set[str] = set()
        for kind in sorted(registry):
            entry = registry[kind]
            sketch = entry.get("sketch") if isinstance(entry, dict) \
                else None
            if not sketch or sketch in seen_sketches:
                continue
            seen_sketches.add(sketch)
            merge = entry.get("merge") \
                or _SKETCH_MERGE_DEFAULT.get(sketch)
            allowed = _MERGE_TO_COLLECTIVE.get(merge)
            if allowed is None:
                continue
            smod = self.project.by_suffix(f"ops/{sketch}.py")
            if smod is None:
                continue
            fid = (smod.name, "merge_registers")
            fn = self.index.functions.get(fid)
            if fn is None:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                leaf = call_chain(node.func)[-1:]
                if leaf and leaf[0] in _MERGE_COLLECTIVES \
                        and leaf[0] not in allowed:
                    out.append(Finding(
                        "mesh", "sketch-merge-mismatch", smod.relpath,
                        node.lineno, f"{sketch}.merge_registers",
                        f"{sketch} registers merge via {leaf[0]} but "
                        f"AGG_CLOSURE declares the {merge!r} register "
                        f"algebra ({sorted(allowed)}); "
                        f"{'summing' if leaf[0] == 'psum' else 'folding'}"
                        f" registers with the wrong operator corrupts "
                        f"every cross-chip cardinality silently"))
        return out

    # -- merge-op-mismatch -----------------------------------------------------
    def merge_op_findings(self) -> List[Finding]:
        out: List[Finding] = []
        idx = self.index
        for fid, fn in sorted(idx.functions.items()):
            mi = idx.modules[fid[0]]
            for node in walk_shallow(fn):
                if not isinstance(node, ast.If):
                    continue
                kind = _kind_branch(node.test)
                if kind is None:
                    continue
                expected = {"min": "pmin", "max": "pmax"}[kind]
                for stmt in node.body:
                    for sub in ast.walk(stmt):
                        if not isinstance(sub, ast.Call):
                            continue
                        leaf = call_chain(sub.func)[-1:]
                        if leaf and leaf[0] in _MERGE_COLLECTIVES \
                                and leaf[0] != expected:
                            out.append(Finding(
                                "mesh", "merge-op-mismatch",
                                mi.mod.relpath, sub.lineno,
                                f"{fid[1]}:{kind}",
                                f"{fid[1]} merges kind == {kind!r} "
                                f"partials with {leaf[0]}; extrema "
                                f"merge with {expected} — "
                                f"{leaf[0]} over per-chip "
                                f"{kind}s returns garbage whenever "
                                f"more than one chip holds the group"))
        return out

    # -- host calls / host-state writes in shard bodies ------------------------
    def shard_body_findings(self) -> List[Finding]:
        out: List[Finding] = []
        idx = self.index
        for fid in sorted(self.reachable):
            fn = idx.functions.get(fid)
            if fn is None:
                continue
            mi = idx.modules[fid[0]]
            path = mi.mod.relpath
            site = self.roots.get(fid)
            via = f" (sharded via {site[0]}:{site[1]})" if site else ""
            local_names = _local_bindings(fn)
            globals_here = self.module_globals.get(fid[0], set())
            global_decls: Set[str] = set()
            for node in walk_shallow(fn):
                if isinstance(node, ast.Global):
                    global_decls.update(node.names)
            for node in walk_shallow(fn):
                if isinstance(node, ast.Call):
                    name = dotted_name(node.func)
                    if name and (name.startswith(_HOST_CALL_PREFIXES)
                                 or name.split(".")[-1]
                                 in _HOST_CALL_LEAVES):
                        out.append(Finding(
                            "mesh", "host-call-in-shard", path,
                            node.lineno, f"{fid[1]}:{name}",
                            f"{fid[1]} runs inside a shard_map body"
                            f"{via} but calls {name}(); host callbacks "
                            f"and untracked RNG break replication — "
                            f"every replica re-enters the host (or "
                            f"diverges), and multi-host runs deadlock "
                            f"or silently disagree"))
                        continue
                    chain = call_chain(node.func)
                    if len(chain) >= 3 and chain[0] == "self" \
                            and chain[-1] in _MUTATORS:
                        out.append(self._write_finding(
                            fid, path, node.lineno, via,
                            f"self.{chain[1]}.{chain[-1]}()"))
                    elif len(chain) == 2 and chain[-1] in _MUTATORS \
                            and chain[0] in globals_here \
                            and chain[0] not in local_names:
                        out.append(self._write_finding(
                            fid, path, node.lineno, via,
                            f"{chain[0]}.{chain[-1]}()"))
                elif isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = node.targets \
                        if isinstance(node, ast.Assign) else [node.target]
                    for t in targets:
                        w = self._write_target(t, local_names,
                                               globals_here,
                                               global_decls)
                        if w is not None:
                            out.append(self._write_finding(
                                fid, path, node.lineno, via, w))
        return out

    @staticmethod
    def _write_target(t: ast.expr, local_names: Set[str],
                      globals_here: Set[str],
                      global_decls: Set[str]) -> Optional[str]:
        if isinstance(t, ast.Subscript):
            t2 = t.value
            if isinstance(t2, ast.Name) and t2.id in globals_here \
                    and t2.id not in local_names:
                return f"{t2.id}[...]"
            t = t2
        if isinstance(t, ast.Attribute):
            base = call_chain(t)
            if base and base[0] == "self":
                return f"self.{t.attr}"
            return None
        if isinstance(t, ast.Name) and t.id in global_decls:
            return t.id
        return None

    @staticmethod
    def _write_finding(fid: FuncId, path: str, line: int, via: str,
                       what: str) -> Finding:
        return Finding(
            "mesh", "host-state-write-in-shard", path, line,
            f"{fid[1]}:{what}",
            f"{fid[1]} runs inside a shard_map body{via} but writes "
            f"host state ({what}); the body traces once, so the write "
            f"happens at trace time on every host — stats/caches/"
            f"registries mutated here diverge from what actually "
            f"executed per shard")


def _kind_branch(test: ast.expr) -> Optional[str]:
    """``<x>.kind == "min"`` / ``kind == "max"`` comparison -> the
    literal, else None."""
    if not (isinstance(test, ast.Compare) and len(test.ops) == 1
            and isinstance(test.ops[0], ast.Eq)):
        return None
    left, right = test.left, test.comparators[0]
    for a, b in ((left, right), (right, left)):
        named = (isinstance(a, ast.Attribute) and a.attr == "kind") \
            or (isinstance(a, ast.Name) and a.id == "kind")
        if named and isinstance(b, ast.Constant) \
                and b.value in ("min", "max"):
            return b.value
    return None


def _local_bindings(fn: ast.FunctionDef) -> Set[str]:
    out = {a.arg for a in fn.args.posonlyargs + fn.args.args
           + fn.args.kwonlyargs}
    if fn.args.vararg is not None:
        out.add(fn.args.vararg.arg)
    if fn.args.kwarg is not None:
        out.add(fn.args.kwarg.arg)
    def bind(t: ast.expr) -> None:
        # Subscript/Attribute stores mutate an EXISTING object — they
        # bind nothing (and their base name must stay visible to the
        # host-global-write check)
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                bind(e)
        elif isinstance(t, ast.Starred):
            bind(t.value)

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                bind(t)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            for n in ast.walk(node.target):
                if isinstance(n, ast.Name):
                    out.add(n.id)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if item.optional_vars is not None:
                    for n in ast.walk(item.optional_vars):
                        if isinstance(n, ast.Name):
                            out.add(n.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.add(node.name)
    return out


def run(project: Project) -> List[Finding]:
    m = _Mesh(project)
    out = m.axis_findings()
    out.extend(m.sketch_findings())
    out.extend(m.merge_op_findings())
    out.extend(m.shard_body_findings())
    return out
