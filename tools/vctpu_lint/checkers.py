"""The vctpu-lint checker suite: five codes, five hard-won invariants.

Each checker's docstring names the historical incident it encodes; the
full catalog (with suppression policy and how to add a checker) is
docs/static_analysis.md.
"""

from __future__ import annotations

import ast
import json
import os

from tools.vctpu_lint import Checker, register
from tools.vctpu_lint import project as project_mod

#: the one module allowed to read VCTPU_* environment variables
KNOB_REGISTRY_PATH = "variantcalling_tpu/knobs.py"

#: dotted module of the designated degradation recorder (VCT002)
_DEGRADE_MODULE = "variantcalling_tpu.utils.degrade"

#: the one function allowed to reduce over the tree/margin axis
SEQUENTIAL_TREE_SUM = "sequential_tree_sum"

#: identifier tokens that mark an array as per-tree/margin data (VCT003)
_TREE_TOKENS = {"tree", "trees", "margin", "margins", "pertree"}

#: sanctioned degradation-recorder calls (VCT002): module.attr spellings
_DEGRADE_CALLS = {("degrade", "record")}

#: library paths where ad-hoc wall-clock timing is sanctioned (VCT006):
#: the obs subsystem and the trace module ARE the timing layer
_TIMING_EXEMPT = ("variantcalling_tpu/obs/", "variantcalling_tpu/utils/trace.py")

#: the committed obs event-schema artifact VCT007 checks against
_EVENT_SCHEMA_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "variantcalling_tpu", "obs", "event_schema.json")


def _is_environ(node: ast.expr) -> bool:
    """True for ``os.environ`` / bare ``environ`` (any import spelling)."""
    if isinstance(node, ast.Attribute):
        return node.attr == "environ"
    return isinstance(node, ast.Name) and node.id == "environ"


def _const_str(node: ast.expr) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


@register
class RawEnvironChecker(Checker):
    """VCT001 — a ``VCTPU_*`` environment read outside the typed knob
    registry.

    Incident: before PR 4 the tree had ~39 ad-hoc ``os.environ`` reads in
    14 modules, each with its own parse, default and failure mode — a
    malformed value crashed mid-run on one engine and was silently
    ignored on another, and a typo'd name configured nothing at all.
    ``variantcalling_tpu/knobs.py`` is now the single parse point
    (declared type/default/validator, malformed values exit 2 on every
    engine, unknown names warn at startup); everything else must go
    through it.
    """

    code = "VCT001"
    name = "raw-environ"
    description = "VCTPU_* environment read outside variantcalling_tpu/knobs.py"

    def applies_to(self, path: str) -> bool:
        return not path.endswith(KNOB_REGISTRY_PATH)

    def _flag_if_knob(self, node: ast.AST, key: ast.expr | None) -> None:
        name = _const_str(key) if key is not None else None
        if name is not None and name.startswith("VCTPU_"):
            self.report(node, f"raw environment read of {name} — declare it "
                              "in variantcalling_tpu/knobs.py and use "
                              "knobs.get/get_bool/get_int/get_float/get_str")

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in ("get", "pop", "setdefault") \
                and _is_environ(func.value) and node.args:
            self._flag_if_knob(node, node.args[0])
        elif (isinstance(func, ast.Name) and func.id == "getenv") \
                or (isinstance(func, ast.Attribute) and func.attr == "getenv"):
            if node.args:
                self._flag_if_knob(node, node.args[0])
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if _is_environ(node.value):
            self._flag_if_knob(node, node.slice)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        # "VCTPU_X" in os.environ / not in os.environ
        if len(node.ops) == 1 and isinstance(node.ops[0], (ast.In, ast.NotIn)) \
                and _is_environ(node.comparators[0]):
            self._flag_if_knob(node, node.left)
        self.generic_visit(node)


@register
class SilentFallbackChecker(Checker):
    """VCT002 — a broad ``except`` that swallows and continues.

    Incident: the round-5 byte-parity flake traced to
    ``_native_cpu_featurize_score`` returning None on ANY exception (a
    bare except around the native build), silently flipping the scoring
    engine per call under suite load. PR 2's contract: degradation is
    either loud (re-raise / EngineError, exit 2) or recorded
    (``utils.degrade.record`` — visible in the log and the in-process
    event trail). A broad handler that does neither is this finding.
    """

    code = "VCT002"
    name = "silent-fallback"
    description = ("except:/except Exception: swallows without re-raising, "
                   "raising EngineError, or calling degrade.record")

    def __init__(self, path: str, lines: list[str], project=None):
        super().__init__(path, lines, project)
        #: (owner, attr) call spellings that count as degrade.record —
        #: the default plus whatever this module's imports alias it to
        self._degrade_attrs: set[tuple[str, str]] = set(_DEGRADE_CALLS)
        #: bare-name spellings (``from ...degrade import record as r``)
        self._degrade_names: set[str] = set()

    def visit_Module(self, node: ast.Module) -> None:
        # resolve the recorder through the module's OWN import spellings
        # (shared project-model resolution, not the one hard-coded
        # ``degrade.record`` shape): a degrade path reached through
        # ``from variantcalling_tpu.utils.degrade import record as _rec``
        # used to be invisible and the handler got flagged anyway
        for n in ast.walk(node):
            if isinstance(n, ast.Import):
                for alias in n.names:
                    if alias.name == _DEGRADE_MODULE:
                        local = alias.asname or alias.name.split(".")[-1]
                        self._degrade_attrs.add((local, "record"))
            elif isinstance(n, ast.ImportFrom) and n.module:
                for alias in n.names:
                    if n.module == _DEGRADE_MODULE and alias.name == "record":
                        self._degrade_names.add(alias.asname or "record")
                    elif alias.name == "degrade" and \
                            f"{n.module}.degrade" == _DEGRADE_MODULE:
                        self._degrade_attrs.add(
                            (alias.asname or "degrade", "record"))
        self.generic_visit(node)

    @staticmethod
    def _is_broad(handler: ast.ExceptHandler) -> bool:
        def broad_name(n: ast.expr) -> bool:
            return isinstance(n, ast.Name) and n.id in ("Exception", "BaseException")

        if handler.type is None:
            return True
        if broad_name(handler.type):
            return True
        return isinstance(handler.type, ast.Tuple) \
            and any(broad_name(e) for e in handler.type.elts)

    def _is_compliant(self, handler: ast.ExceptHandler) -> bool:
        for stmt in handler.body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Raise):
                    return True
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if isinstance(func, ast.Name) and \
                        func.id in self._degrade_names:
                    return True
                if isinstance(func, ast.Attribute):
                    owner = func.value
                    owner_name = owner.id if isinstance(owner, ast.Name) else \
                        owner.attr if isinstance(owner, ast.Attribute) else ""
                    if (owner_name, func.attr) in self._degrade_attrs:
                        return True
                if self._routes_to_degrade(func):
                    return True
        return False

    def _routes_to_degrade(self, func: ast.expr) -> bool:
        """Project-aware compliance: the handler calls a helper from
        which ``utils.degrade.record`` is transitively reachable over the
        resolved call graph — a degrade path one call away (e.g. the
        retry bookkeeping helpers pool tasks route failures through) used
        to be invisible to the per-file view and got flagged anyway."""
        if self.project is None:
            return False
        target = self.project.function_key(_DEGRADE_MODULE, "record")
        if target is None:
            return False
        name = func.id if isinstance(func, ast.Name) \
            else project_mod._dotted(func)
        if not name:
            return False
        got = self.project.resolve_name(self.path, name)
        return got is not None and self.project.reaches(got, target)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if self._is_broad(node) and not self._is_compliant(node):
            what = "bare except" if node.type is None else \
                f"except {ast.unparse(node.type)}"
            self.report(node, f"{what} swallows and continues — re-raise, "
                              "raise EngineError, or route through "
                              "utils.degrade.record(...)")
        self.generic_visit(node)


@register
class UnorderedReductionChecker(Checker):
    """VCT003 — an unordered reduction over a tree/margin axis.

    Incident: the round-5 multihost parity flake's root cause — XLA
    reassociates f32 ``jnp.sum`` reductions, so the tree-margin sum
    drifted by 1 ulp across device counts and engines. PR 2 pinned ALL
    margin reductions to canonical sequential tree order through the one
    shared ``forest.sequential_tree_sum``; any other ``jnp.sum``/
    ``.sum()`` over an array named like per-tree/margin data can
    reintroduce the drift.
    """

    code = "VCT003"
    name = "unordered-reduction"
    description = ("jnp.sum/.sum over a tree/margin-named axis outside "
                   "forest.sequential_tree_sum")

    def __init__(self, path: str, lines: list[str], project=None):
        super().__init__(path, lines, project)
        self._func_stack: list[str] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    @staticmethod
    def _tree_named(expr: ast.expr) -> str | None:
        """The first identifier in ``expr`` whose _-tokens hit the
        tree/margin vocabulary, or None."""
        for node in ast.walk(expr):
            name = None
            if isinstance(node, ast.Name):
                name = node.id
            elif isinstance(node, ast.Attribute):
                name = node.attr
            elif isinstance(node, ast.arg):
                name = node.arg
            if name and _TREE_TOKENS & set(name.lower().split("_")):
                return name
        return None

    def visit_Call(self, node: ast.Call) -> None:
        if SEQUENTIAL_TREE_SUM in self._func_stack:
            self.generic_visit(node)
            return
        func = node.func
        operand: ast.expr | None = None
        if isinstance(func, ast.Attribute) and func.attr == "sum":
            owner = func.value
            if isinstance(owner, ast.Name) and owner.id in ("jnp", "np", "numpy", "jax"):
                operand = node.args[0] if node.args else None
            else:
                operand = owner  # method form: per_tree.sum(axis=...)
        if operand is not None:
            hit = self._tree_named(operand)
            if hit is not None:
                self.report(node, f"unordered sum over {hit!r} — per-tree/"
                                  "margin reductions must go through "
                                  "forest.sequential_tree_sum (XLA "
                                  "reassociation drifts f32 bits)")
        self.generic_visit(node)


@register
class TracerHostSyncChecker(Checker):
    """VCT004 — host synchronization inside a jitted function.

    Incident class: ``.item()`` / ``float()`` / ``np.asarray`` on a
    tracer either fails at trace time (ConcretizationTypeError, often
    only on the accelerator path that actually jits) or — worse, via
    ``io_callback``-style escapes — forces a device sync per call in the
    hot loop. The engine contract keeps device programs pure: fetch once
    at the boundary, finalize on the host (``forest.finalize_margin``).
    """

    code = "VCT004"
    name = "tracer-host-sync"
    description = (".item()/float()/np.asarray on values inside "
                   "@jax.jit/pjit-decorated functions")

    _SYNC_METHODS = ("item", "tolist", "block_until_ready")
    _SYNC_BUILTINS = ("float", "int", "bool", "complex")

    @staticmethod
    def _is_jit_expr(expr: ast.expr) -> bool:
        """jit / jax.jit / pjit / partial(jax.jit, ...) / jax.jit(...)"""
        if isinstance(expr, ast.Name):
            return expr.id in ("jit", "pjit")
        if isinstance(expr, ast.Attribute):
            return expr.attr in ("jit", "pjit")
        if isinstance(expr, ast.Call):
            func = expr.func
            if isinstance(func, (ast.Name, ast.Attribute)):
                fname = func.id if isinstance(func, ast.Name) else func.attr
                if fname == "partial":
                    return bool(expr.args) and \
                        TracerHostSyncChecker._is_jit_expr(expr.args[0])
                return fname in ("jit", "pjit")
        return False

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        if any(self._is_jit_expr(d) for d in node.decorator_list):
            self._scan_jit_body(node)
        else:
            self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def _scan_jit_body(self, func: ast.FunctionDef) -> None:
        for node in ast.walk(func):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                if f.attr in self._SYNC_METHODS:
                    self.report(node, f".{f.attr}() inside @jit-decorated "
                                      f"'{func.name}' forces a host sync / "
                                      "fails on tracers — fetch outside the "
                                      "jitted program")
                elif f.attr in ("asarray", "array") and \
                        isinstance(f.value, ast.Name) and \
                        f.value.id in ("np", "numpy"):
                    self.report(node, f"np.{f.attr}() inside @jit-decorated "
                                      f"'{func.name}' materializes on host — "
                                      "use jnp inside traced code")
                elif f.attr == "device_get":
                    self.report(node, f"device_get inside @jit-decorated "
                                      f"'{func.name}'")
            elif isinstance(f, ast.Name) and f.id in self._SYNC_BUILTINS \
                    and node.args and not isinstance(node.args[0], ast.Constant):
                self.report(node, f"{f.id}() on a traced value inside "
                                  f"@jit-decorated '{func.name}' raises "
                                  "ConcretizationTypeError at trace time")


@register
class UnboundedSubprocessChecker(Checker):
    """VCT005 — an external process or worker thread with no bounded wait.

    Incident class: the streaming executor's watchdog exists because a
    wedged stage (native build under load, a stuck beagle, a device
    runtime that never answers) turns a pipeline into a zombie. Every ``subprocess`` call carries ``timeout=``; every
    ``Popen`` has a ``communicate(timeout=)``/``wait(timeout=)`` in its
    function. (The non-daemon-thread clause this checker used to carry
    moved wholesale into VCT010 rule 2, which is strictly stricter —
    outside ``parallel/pipeline.py`` a join path does not excuse a
    non-daemon worker — and one defect must not yield two findings
    needing two suppression codes.)
    """

    code = "VCT005"
    name = "unbounded-subprocess"
    description = "subprocess call without timeout= or bounded wait"

    _WAIT_FNS = ("run", "call", "check_output", "check_call")

    def __init__(self, path: str, lines: list[str], project=None):
        super().__init__(path, lines, project)
        self._func_stack: list[ast.AST] = []

    def visit_Module(self, node: ast.Module) -> None:
        self._module = node
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._func_stack.append(node)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _enclosing_has_bounded_wait(self) -> bool:
        scope = self._func_stack[-1] if self._func_stack else self._module
        for n in ast.walk(scope):
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                    and n.func.attr in ("communicate", "wait") \
                    and any(kw.arg == "timeout" for kw in n.keywords):
                return True
        return False

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name) and \
                func.value.id == "subprocess":
            if func.attr in self._WAIT_FNS:
                if not any(kw.arg == "timeout" for kw in node.keywords):
                    self.report(node, f"subprocess.{func.attr} without "
                                      "timeout= can hang the pipeline "
                                      "forever — bound it (see "
                                      "VCTPU_SUBPROC_TIMEOUT_S)")
            elif func.attr == "Popen" and not self._enclosing_has_bounded_wait():
                self.report(node, "subprocess.Popen with no "
                                  "communicate(timeout=)/wait(timeout=) in "
                                  "this function")
        self.generic_visit(node)


@register
class RawTimingChecker(Checker):
    """VCT006 — ad-hoc wall-clock timing in library code outside the
    obs/trace layer.

    Incident class: before the obs subsystem (ISSUE 5) the tree had grown
    four disconnected timing idioms — ``trace.py`` spans, the reference's
    broken decorator, per-module ``time.time()`` deltas logged as free
    text, and a benchmark script's own stopwatches. A raw ``time.time()`` /
    ``time.perf_counter()`` measurement in library code is invisible to
    ``vctpu obs``: it cannot land in the run stream, the summary, or the
    Perfetto export, and it silently re-fragments the telemetry layer.
    Wrap the region in ``trace.stage(...)`` (spans flow into obs) or
    record through ``obs.span``/metrics; sanctioned low-level sites carry
    a per-line suppression naming why.

    Scope: ``variantcalling_tpu/`` only (the library), minus ``obs/`` and
    ``utils/trace.py`` — which ARE the timing layer. ``time.monotonic``
    deadline checks (watchdogs) and ``time.sleep`` are not timing and are
    not flagged.
    """

    code = "VCT006"
    name = "raw-timing"
    description = ("time.time()/time.perf_counter() timing in library code "
                   "outside obs/trace spans")

    _CLOCKS = ("time", "perf_counter", "perf_counter_ns", "process_time")

    def __init__(self, path: str, lines: list[str], project=None):
        super().__init__(path, lines, project)
        # any-import-spelling tracking (the VCT001 `_is_environ` rule):
        # `import time as _time` and `from time import perf_counter as pc`
        # must not evade the checker
        self._time_aliases: set[str] = {"time"}
        self._clock_names: set[str] = set()

    def applies_to(self, path: str) -> bool:
        if not path.startswith("variantcalling_tpu/"):
            return False  # tools/tests/benchmarks own their stopwatches
        return not any(path.startswith(x) or path.endswith(x)
                       for x in _TIMING_EXEMPT)

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            if alias.name == "time":
                self._time_aliases.add(alias.asname or "time")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "time":
            for alias in node.names:
                if alias.name in self._CLOCKS:
                    self._clock_names.add(alias.asname or alias.name)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        clock = None
        if isinstance(func, ast.Attribute) and func.attr in self._CLOCKS \
                and isinstance(func.value, ast.Name) \
                and func.value.id in self._time_aliases:
            clock = f"{func.value.id}.{func.attr}"
        elif isinstance(func, ast.Name) and func.id in self._clock_names:
            clock = func.id  # from time import perf_counter [as pc]
        if clock is not None:
            self.report(node, f"raw {clock}() timing in library code — "
                              "route it through trace.stage(...)/obs.span so "
                              "the measurement lands in the run telemetry "
                              "stream (docs/observability.md)")
        self.generic_visit(node)


@register
class UndeclaredEventKindChecker(Checker):
    """VCT007 — an obs event emitted with a kind the committed schema
    does not declare.

    Incident class: the obs contract lives in the COMMITTED
    ``variantcalling_tpu/obs/event_schema.json`` — the tier-0 schema
    stage, the exporters and external consumers all validate against
    that one artifact. The tier-0 stage only exercises the producers it
    generates, so a NEW ``obs.event("brand_new_kind", ...)`` call deep
    in a pipeline would ship events no consumer recognizes and no
    schema review ever saw (the PR 6 ``profile`` kind landed exactly
    this way — code first, schema almost forgotten). This checker makes
    the artifact the source of truth at lint time: every string-literal
    kind passed to ``obs.event(...)`` / ``*._emit(...)`` must exist in
    the committed ``kinds`` table; adding a kind is a reviewable diff to
    the schema file FIRST.

    Non-literal kinds are not flagged (the schema validator still
    catches them at the tier-0 stage / in tests).
    """

    code = "VCT007"
    name = "undeclared-event-kind"
    description = ("obs.event/._emit called with an event kind missing from "
                   "the committed event_schema.json")

    _schema_kinds: frozenset[str] | None = None

    @classmethod
    def schema_kinds(cls) -> frozenset[str]:
        if cls._schema_kinds is None:
            try:
                with open(_EVENT_SCHEMA_PATH, encoding="utf-8") as fh:
                    cls._schema_kinds = frozenset(json.load(fh)["kinds"])
            except (OSError, ValueError, KeyError):
                # a missing/garbled artifact is the schema stage's finding,
                # not a reason to flag every emit site
                cls._schema_kinds = frozenset()
        return cls._schema_kinds

    def applies_to(self, path: str) -> bool:
        # producers live in the library and tools; tests exercise
        # deliberately-bogus kinds
        return not path.startswith("tests/")

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        is_emit = False
        if isinstance(func, ast.Attribute):
            if func.attr == "event" and isinstance(func.value, ast.Name) \
                    and func.value.id == "obs":
                is_emit = True  # obs.event("kind", "name", ...)
            elif func.attr == "_emit":
                is_emit = True  # run._emit("kind", "name", {...})
        if is_emit and node.args:
            kind = _const_str(node.args[0])
            kinds = self.schema_kinds()
            if kind is not None and kinds and kind not in kinds:
                self.report(node, f"event kind {kind!r} is not declared in "
                                  "variantcalling_tpu/obs/event_schema.json — "
                                  "add it to the committed schema (a "
                                  "reviewable diff) before emitting it")
        self.generic_visit(node)


#: the one function allowed to write bytes to a streaming output sink
#: (VCT008): retry-wrapped + rewind-guarded, called only by the committer
_SANCTIONED_SINK_WRITER = "_sink_write"

#: receiver-name tokens that mark a handle/path as streaming OUTPUT state
#: (VCT008): the committer's sink and the .partial file handle
_SINK_TOKENS = ("sink", "partial")


@register
class UnsequencedWriteChecker(Checker):
    """VCT008 — an unsequenced write to a streaming output path.

    Invariant from the parallel host-IO PR (docs/streaming_executor.md
    "Parallel host IO"): with ingest, scoring and BGZF compression fanned
    out across worker pools, every byte that reaches a streaming OUTPUT
    path must flow through the ONE sequenced committer —
    ``_sink_write`` (bounded retry + rewind guard) draining chunks in
    sequence order — and the destination is only ever touched by the
    single sanctioned ``os.replace`` atomic commit. A direct
    ``sink.write(...)`` bypasses the retry/rewind contract (a transient
    ENOSPC then duplicates or drops bytes mid-file), and a second
    ``os.replace`` onto an output path can commit a torn or
    out-of-order file. Scope: ``variantcalling_tpu/pipelines/`` (the
    layer that owns streaming output paths); report writers and io/
    writer classes are the sanctioned layer below — EXCEPT functions the
    project index registers as pool tasks submitted FROM a pipelines
    module (with the whole per-chunk body fanned out on the IO pool, a
    sink write inside such a task is a pipeline write wherever the
    function happens to live). Sanctioned sites carry inline
    suppressions naming why, like VCT006's.
    """

    code = "VCT008"
    name = "unsequenced-write"
    description = ("direct sink/partial write or os.replace on a streaming "
                   "output path outside the sanctioned committer")

    def __init__(self, path: str, lines: list[str], project=None):
        super().__init__(path, lines, project)
        self._funcs: list[str] = []
        self._qual: list[str] = []
        #: qualnames (in this module) of pool tasks submitted from
        #: pipelines code — outside pipelines/, ONLY these are in scope
        self._task_quals: set[str] = set()
        if project is not None and "variantcalling_tpu/pipelines/" not in path:
            self._task_quals = project.pipeline_submitted_tasks(path)

    def applies_to(self, path: str) -> bool:
        return "variantcalling_tpu/pipelines/" in path or bool(self._task_quals)

    def _in_scope(self) -> bool:
        if "variantcalling_tpu/pipelines/" in self.path:
            return True
        qual = ".".join(self._qual)
        return any(qual == t or qual.startswith(t + ".")
                   for t in self._task_quals)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._funcs.append(node.name)
        self._qual.append(node.name)
        self.generic_visit(node)
        self._qual.pop()
        self._funcs.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._qual.append(node.name)
        self.generic_visit(node)
        self._qual.pop()

    @staticmethod
    def _sink_named(expr: ast.expr) -> str | None:
        name = None
        if isinstance(expr, ast.Name):
            name = expr.id
        elif isinstance(expr, ast.Attribute):
            name = expr.attr
        if name is not None and any(t in name.lower() for t in _SINK_TOKENS):
            return name
        return None

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Attribute) and self._in_scope():
            if func.attr == "replace" and isinstance(func.value, ast.Name) \
                    and func.value.id == "os":
                self.report(node, "os.replace in pipeline code — only the "
                                  "streaming committer's single atomic "
                                  "commit may rename onto an output path "
                                  "(suppress at the one sanctioned site)")
            elif func.attr in ("write", "writelines") \
                    and _SANCTIONED_SINK_WRITER not in self._funcs:
                sink = self._sink_named(func.value)
                if sink is not None:
                    self.report(node, f"direct {sink}.{func.attr}() on a "
                                      "streaming output sink — route bytes "
                                      "through the sequenced committer "
                                      f"({_SANCTIONED_SINK_WRITER}: bounded "
                                      "retry + rewind guard, chunk order)")
        self.generic_visit(node)


#: identifier tokens marking an array as margin/score data (VCT009):
#: VCT003's tree/margin vocabulary plus the score spellings the
#: mesh-sharded scoring path moves around
_MARGIN_TOKENS = _TREE_TOKENS | {"score", "scores"}


@register
class ShardMapMarginReductionChecker(Checker):
    """VCT009 — a cross-device (or unordered) reduction over margin/score
    data inside a ``shard_map`` body.

    Incident class: the PR 2 cross-device-count parity flake — XLA
    reassociating f32 margin sums made score bits depend on the device
    count. The mesh-sharded scoring path (parallel/shard_score.py) is
    safe BECAUSE its ``shard_map`` bodies are pure data-parallel maps:
    per-tree margins reduce inside each device's program through the one
    sanctioned ``forest.sequential_tree_sum`` and devices exchange
    nothing. A ``jax.lax.psum`` over margins/scores inside a shard_map
    body reintroduces exactly the incident (a cross-device sum whose
    grouping varies with mesh shape), and a ``jnp.sum``/``.sum()`` there
    is the VCT003 reassociation hole in its most dangerous location.
    Bodies are found structurally: any function (or lambda) passed as
    the first argument to ``shard_map`` / ``shard_program``, plus every
    function nested inside it — resolution (simple-name aliases, aliased
    lambdas, conditional rebinds) is the project model's
    :func:`~tools.vctpu_lint.project.installed_bodies`, shared with the
    whole-program index. With a project index attached, bodies installed
    FROM ANOTHER MODULE (``from here import body; shard_program(body,
    ...)`` elsewhere) are scanned too — the cross-module alias shape the
    per-file view missed.
    """

    code = "VCT009"
    name = "shardmap-margin-reduction"
    description = ("psum/sum over margin/score-named arrays inside a "
                   "shard_map body outside sequential_tree_sum")

    @staticmethod
    def _margin_named(expr: ast.expr) -> str | None:
        for node in ast.walk(expr):
            name = None
            if isinstance(node, ast.Name):
                name = node.id
            elif isinstance(node, ast.Attribute):
                name = node.attr
            elif isinstance(node, ast.arg):
                name = node.arg
            if name and _MARGIN_TOKENS & set(name.lower().split("_")):
                return name
        return None

    def visit_Module(self, node: ast.Module) -> None:
        # pass 1: collect shard_map body functions — first argument of
        # every shard_map/shard_program call (Name reference or inline
        # lambda), aliases resolved transitively through the shared
        # project-model machinery (``fn = body; shard_map(fn, ...)``
        # scans ``body``; conditional rebinds add every source, erring
        # toward scanning too much — suppressions exist for false hits)
        body_names, lambdas = project_mod.installed_bodies(node)
        if self.project is not None:
            # cross-module installs: functions of THIS module registered
            # as shard_map bodies anywhere in the project
            for qual in self.project.traced_bodies_in(self.path):
                body_names.add(qual.split(".")[-1])
        for n in ast.walk(node):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n.name in body_names:
                self._scan_body(n)
        for lam in lambdas:
            self._scan_body(lam)

    def _scan_body(self, func: ast.AST) -> None:
        stack = list(ast.iter_child_nodes(func))
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n.name == SEQUENTIAL_TREE_SUM:
                continue  # the sanctioned merge site: don't descend
            stack.extend(ast.iter_child_nodes(n))
            if not isinstance(n, ast.Call):
                continue
            f = n.func
            fname = f.id if isinstance(f, ast.Name) else \
                f.attr if isinstance(f, ast.Attribute) else ""
            if fname == "psum":
                hit = self._margin_named(n.args[0]) if n.args else None
                if hit is not None:
                    self.report(n, f"psum over {hit!r} inside a shard_map "
                                   "body — a cross-device margin/score sum "
                                   "makes output bits depend on the device "
                                   "count (the PR 2 parity incident); merge "
                                   "per-device margins through "
                                   "forest.sequential_tree_sum and "
                                   "concatenate over dp instead")
            elif fname == "sum":
                operand = None
                if isinstance(f, ast.Attribute):
                    owner = f.value
                    if isinstance(owner, ast.Name) and \
                            owner.id in ("jnp", "np", "numpy", "jax"):
                        operand = n.args[0] if n.args else None
                    else:
                        operand = owner  # method form: margins.sum(...)
                if operand is not None:
                    hit = self._margin_named(operand)
                    if hit is not None:
                        self.report(n, f"unordered sum over {hit!r} inside "
                                       "a shard_map body — XLA reassociates "
                                       "f32 reductions per shard shape; "
                                       "margin/score reductions must go "
                                       "through forest.sequential_tree_sum")


@register
class ConcurrencyDisciplineChecker(Checker):
    """VCT010 — concurrency discipline over the thread-reachable graph.

    Incident class: with the per-chunk body fanned out on the IO pool
    (PR 7) and megabatches scored through shard_map (PR 8), more of the
    tree executes off the main thread every PR — and the last unsequenced-
    write incident was reachable ONLY through a pool task, invisible to
    any per-file checker. Using the project model's thread-entry registry
    (``threading.Thread(target=...)``, ``IoPool``-style ``.submit``,
    ``imap_ordered`` task fns, ``StagePipeline`` stage callables) and the
    resolved call graph, three rules:

    1. **Unlocked shared mutation.** Module/class state mutated from
       thread-reachable code without a lock held and outside the
       sanctioned handoffs — ``queue.Queue`` objects, ``imap_ordered``'s
       ordered reassembly, and the per-thread cells in ``obs/metrics.py``
       (one cell per recording thread, merged at snapshot — sanctioned by
       design, not by lock).
    2. **Non-daemon thread construction** outside ``parallel/pipeline.py``
       — the one module owning the join/watchdog discipline; everywhere
       else a non-daemon worker wedged in a native call blocks process
       exit (the IoPool docstring's rule, now machine-checked).
    3. **Lock-order inversion.** Two locks acquired in both orders
       anywhere in the reachable graph (nested ``with`` blocks, including
       through resolved call edges) — the static shadow of a deadlock.

    Benign racy writes (GIL-atomic diagnostics like
    ``forest.last_strategy``) carry per-line suppressions naming why,
    like VCT006's sanctioned stopwatch sites.

    Scope: the library and tools (everything linted); in snippet mode
    (no project index) the checker builds a throwaway single-module
    index, so fixtures stay one file.
    """

    code = "VCT010"
    name = "concurrency-discipline"
    description = ("unlocked shared mutation from thread-reachable code, "
                   "non-daemon threads outside parallel/pipeline.py, or "
                   "inconsistent lock order")

    def visit_Module(self, node: ast.Module) -> None:
        index = self.project
        if index is None:
            index = project_mod.ProjectIndex.build_single(
                self.path, node, self.lines)
        for path, line, message in index.concurrency_findings():
            if path == self.path:
                self.report(_Anchor(line), message)


#: modules that OWN the run-state filesystem protocol (VCT011): the
#: journal (``.journal``/``.partial`` lifecycle + resume rename), the
#: chunk cache (``.vcc`` mkstemp+replace publish), the elastic lease
#: arbiter (``.lease.gN`` O_EXCL acquire + handoff rename), and
#: rank_plan (the ``.done`` marker sealer + the one seam-merge
#: committer ``splice_segments``). Everything else — including the
#: pipelines — must go through these helpers or the ``_sink_write``
#: committer so crash-recovery sees exactly one naming discipline.
_RUN_STATE_OWNERS = (
    "variantcalling_tpu/io/journal.py",
    "variantcalling_tpu/io/chunk_cache.py",
    "variantcalling_tpu/parallel/elastic.py",
    "variantcalling_tpu/parallel/rank_plan.py",
)

#: the sanctioned output committer (shared with VCT008's rule)
_SANCTIONED_SINK_FN = "_sink_write"


@register
class RunStateProtocolChecker(Checker):
    """VCT011 — run-state filesystem protocol discipline.

    Incident class: the byte-parity story is now enforced by a
    *filesystem protocol* — O_EXCL ``.lease.gN`` acquires, tmp-sibling
    ``os.replace`` commits, ``.done`` markers sealed only after the
    journal's ``finish()`` — scattered across 13 modules. A module that
    opens a ``.partial`` or writes a ``.done`` marker with its own
    spelling bypasses the crash-recovery scan (``_try_resume`` renames,
    marker trust in ``run_scaleout``) silently: the run "succeeds" and
    resumes wrong. Using the project model's filesystem-effect index
    (suffix lineage resolved through path helpers, module constants and
    ``self.attr`` bindings), four rules:

    1. **Ownership.** Any *write* effect whose path lineage carries a
       run-state suffix (``.journal``/``.partial``/``.lease``/``.done``/
       ``.vcc``) outside the owner modules or the ``_sink_write``
       committer.
    2. **Tmp-sibling commits.** Any ``os.replace``/``os.rename`` whose
       SOURCE lineage shows neither a ``.tmp`` sibling, an ``mkstemp``
       result, nor a ``.partial`` being promoted — a non-atomic-idiom
       commit that can expose a torn file.
    3. **O_EXCL leases.** Any ``os.open`` of a ``.lease`` path without
       ``O_EXCL`` in its flags — a lease acquire that two workers can
       both win.
    4. **Marker-before-finish.** A ``.done`` marker written before the
       journal ``finish()`` in the same function's statement order —
       the marker would claim completion while the journal still says
       in-flight.

    Scope: the library and tools, tests excluded (fixtures deliberately
    misuse the protocol). Snippet mode builds a throwaway single-module
    index so golden fixtures stay one file.
    """

    code = "VCT011"
    name = "run-state-protocol"
    description = ("run-state suffix write outside the sanctioned "
                   "helpers, non-tmp-sibling os.replace, lease acquire "
                   "without O_EXCL, or .done marker before journal "
                   "finish()")

    def applies_to(self, path: str) -> bool:
        return "tests/" not in path and not path.startswith("test")

    def visit_Module(self, node: ast.Module) -> None:
        index = self.project
        if index is None:
            index = project_mod.ProjectIndex.build_single(
                self.path, node, self.lines)
        run_state = frozenset(project_mod.RUN_STATE_SUFFIXES)
        own = [e for e in index.fs_effects() if e.module == self.path]
        is_owner = any(self.path.endswith(p) for p in _RUN_STATE_OWNERS)
        for e in own:
            anchor = _Anchor(e.line)
            suffixes = sorted(e.tokens & run_state)
            in_sink = e.qualname.split(".")[-1] == _SANCTIONED_SINK_FN
            if e.write and suffixes and not is_owner and not in_sink:
                self.report(anchor,
                            f"{e.op} writes a run-state path "
                            f"({'/'.join(suffixes)}) outside the "
                            "sanctioned protocol owners — route through "
                            "io.journal / io.chunk_cache / "
                            "parallel.elastic / parallel.rank_plan so "
                            "crash recovery sees one naming discipline")
            if e.op == "replace" and not (
                    e.src_tokens & project_mod.TMP_SOURCE_TOKENS):
                self.report(anchor,
                            "os.replace source lacks the tmp-sibling "
                            "idiom — write to a '.tmp' sibling (or "
                            "mkstemp/.partial) and replace it so a "
                            "crash never exposes a torn file")
            if e.op == "os.open" and ".lease" in e.tokens \
                    and "O_EXCL" not in e.flags:
                self.report(anchor,
                            "lease acquire without O_EXCL — two workers "
                            "can both win this open; the elastic "
                            "protocol's mutual exclusion rests on "
                            "O_CREAT|O_EXCL failing for the loser")
        # rule 4: per function, a .done marker effect (or write_marker
        # call) textually before a journal finish() call
        self._marker_order(index, own)

    def _marker_order(self, index, own_effects) -> None:
        marker_lines: dict[str, list[int]] = {}
        for e in own_effects:
            if e.write and ".done" in e.tokens:
                marker_lines.setdefault(e.qualname, []).append(e.line)
        info = index.modules.get(self.path)
        if info is None:
            return
        for fn in info.functions.values():
            finishes: list[int] = []
            for n in project_mod._walk_own_scope(fn.node):
                if not isinstance(n, ast.Call):
                    continue
                f = n.func
                if isinstance(f, ast.Attribute) and f.attr == "write_marker":
                    marker_lines.setdefault(fn.qualname, []).append(n.lineno)
                elif isinstance(f, ast.Name) and f.id == "write_marker":
                    marker_lines.setdefault(fn.qualname, []).append(n.lineno)
                elif isinstance(f, ast.Attribute) and f.attr == "finish":
                    owner = f.value
                    oname = owner.id if isinstance(owner, ast.Name) else \
                        owner.attr if isinstance(owner, ast.Attribute) else ""
                    if "journal" in oname.lower() or "jrn" in oname.lower():
                        finishes.append(n.lineno)
            marks = marker_lines.get(fn.qualname, ())
            if marks and finishes:
                first_mark = min(marks)
                if any(fin > first_mark for fin in finishes):
                    self.report(_Anchor(first_mark),
                                ".done marker written before the journal "
                                "finish() in this function — the marker "
                                "claims completion while the journal "
                                "still says in-flight; finish() first, "
                                "then seal the marker")


#: the sequenced-commit byte sinks (VCT012): every function whose output
#: bytes reach the committed artifact — the sink committer, the VCF
#: renderer, the BGZF compressors, and the seam-merge splicer
_BYTE_SINKS = (
    ("variantcalling_tpu.pipelines.filter_variants", "_sink_write"),
    ("variantcalling_tpu.io.vcf", "render_table_bytes_python"),
    ("variantcalling_tpu.io.bgzf", "compress_block"),
    ("variantcalling_tpu.io.bgzf", "BgzfChunkCompressor.add"),
    ("variantcalling_tpu.io.bgzf", "BgzfChunkCompressor.finish"),
    ("variantcalling_tpu.parallel.rank_plan", "splice_segments"),
)

#: knob-registry getter methods whose first argument is the knob name
_KNOB_GETTERS = ("get", "get_bool", "get_int", "get_float", "get_str", "raw")

#: the committed byte-influence contract VCT012 checks against
_KNOBS_CONTRACT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "knobs_contract.json")

_CONTRACT_CLASSES = ("scoring", "byte_neutral")


@register
class ByteInfluenceTaintChecker(Checker):
    """VCT012 — byte-influence taint from knob reads to commit sinks.

    Incident class: PR 18 added a whole scoring family behind new knobs;
    nothing but reviewer diligence noticed that a knob reaching the
    chunk body changes committed bytes and therefore must ride the
    ``##vctpu_knobs=`` provenance header. This checker closes that gap
    mechanically: walk the resolved call graph backward from the
    sequenced-commit sinks (the ``_sink_write`` committer, the VCF
    renderer, the BGZF compressors, the seam-merge splicer); any
    ``knobs.get*("VCTPU_X")`` read inside that backward cone is
    *byte-reaching* and must be declared in the committed
    ``knobs_contract.json`` as either

    - ``scoring`` — changes bytes by design, and therefore MUST carry
      ``in_header=True`` in the registry so runs are reproducible from
      the artifact alone, or
    - ``byte_neutral`` — proven not to change committed bytes (cache
      on/off, pool sizing, observability), with the reason recorded.

    Findings: an unclassified byte-reaching knob; a ``scoring`` knob
    not in the provenance header; a contract entry for a knob the
    registry no longer defines (stale contract); an invalid class.

    Scope: the library and tools, tests excluded. In snippet mode the
    fixture names its fake module after the real sink module (e.g. a
    sources dict keyed ``variantcalling_tpu/io/bgzf.py``) so the sink
    resolution works unchanged.
    """

    code = "VCT012"
    name = "byte-influence-taint"
    description = ("knob read reaching a sequenced-commit byte sink "
                   "without a knobs_contract.json classification, or a "
                   "scoring knob missing in_header provenance")

    _contract_cache: dict | None = None

    @classmethod
    def contract(cls) -> dict:
        if cls._contract_cache is None:
            try:
                with open(_KNOBS_CONTRACT_PATH, encoding="utf-8") as fh:
                    cls._contract_cache = json.load(fh).get("knobs", {})
            except (OSError, ValueError):
                cls._contract_cache = {}
        return cls._contract_cache

    def applies_to(self, path: str) -> bool:
        return "tests/" not in path and not path.startswith("test")

    def visit_Module(self, node: ast.Module) -> None:
        index = self.project
        if index is None:
            index = project_mod.ProjectIndex.build_single(
                self.path, node, self.lines)
        sinks = frozenset(
            k for k in (index.function_key(mod, qual)
                        for mod, qual in _BYTE_SINKS) if k is not None)
        if not sinks:
            cone: frozenset = frozenset()
        else:
            cone = frozenset(index.callers_closure(sinks))
        info = index.modules.get(self.path)
        if info is None:
            return
        contract = self.contract()
        if self.path.endswith("knobs.py"):
            self._registry_rules(node, contract)
            return
        for fn in info.functions.values():
            if fn.key not in cone:
                continue
            for n in project_mod._walk_own_scope(fn.node):
                knob = self._knob_read(info, n)
                if knob is None:
                    continue
                entry = contract.get(knob)
                if entry is None:
                    self.report(n, f"knob {knob!r} read on a byte-"
                                   "reaching path (this function reaches "
                                   "a sequenced-commit sink) but is not "
                                   "classified in knobs_contract.json — "
                                   "declare it 'scoring' (and put it in "
                                   "the provenance header) or "
                                   "'byte_neutral' with a reason")
                elif entry.get("class") not in _CONTRACT_CLASSES:
                    self.report(n, f"knob {knob!r} has invalid contract "
                                   f"class {entry.get('class')!r} — must "
                                   "be 'scoring' or 'byte_neutral'")

    @staticmethod
    def _knob_read(info, node) -> str | None:
        """The knob-name literal if ``node`` is a registry read."""
        if not isinstance(node, ast.Call) or not node.args:
            return None
        name = _const_str(node.args[0])
        if name is None or not name.startswith("VCTPU_"):
            return None
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _KNOB_GETTERS:
            owner = f.value
            if isinstance(owner, ast.Name):
                oname = owner.id
                target = info.imports.get(oname) or \
                    ".".join(info.from_imports.get(oname, ("", "")))
                if oname == "knobs" or "knobs" in (target or ""):
                    return name
        elif isinstance(f, ast.Name) and f.id in _KNOB_GETTERS:
            src = info.from_imports.get(f.id)
            if src and "knobs" in src[0]:
                return name
        return None

    def _registry_rules(self, node: ast.Module, contract: dict) -> None:
        """Inside knobs.py: cross-check the registry vs the contract —
        scoring entries must ride the provenance header, header knobs
        must not be declared byte_neutral, contract names must exist."""
        registered: dict[str, tuple[ast.Call, bool]] = {}
        for n in ast.walk(node):
            if not (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Name)
                    and n.func.id == "_k" and n.args):
                continue
            kname = _const_str(n.args[0])
            if kname is None:
                continue
            in_header = any(
                kw.arg == "in_header"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value is True for kw in n.keywords)
            registered[kname] = (n, in_header)
        if not registered:
            # a knobs.py with zero _k registrations is a test fixture,
            # not the registry — the contract-vs-registry integrity of
            # the REAL module is covered by its own regression test
            return
        for kname, entry in sorted(contract.items()):
            if kname not in registered:
                self.report(_Anchor(1),
                            f"knobs_contract.json entry {kname!r} names "
                            "a knob the registry no longer defines — "
                            "prune the stale contract entry")
                continue
            call, in_header = registered[kname]
            cls_ = entry.get("class")
            if cls_ == "scoring" and not in_header:
                self.report(call,
                            f"knob {kname!r} is contracted 'scoring' "
                            "(changes committed bytes) but lacks "
                            "in_header=True — scoring knobs must ride "
                            "the ##vctpu_knobs= provenance header")
            elif cls_ == "byte_neutral" and in_header:
                self.report(call,
                            f"knob {kname!r} is contracted "
                            "'byte_neutral' yet rides the provenance "
                            "header — either it changes bytes (contract "
                            "it 'scoring') or it should not be in the "
                            "header")


class _Anchor:
    """Minimal node stand-in anchoring a project-level finding to a line."""

    def __init__(self, lineno: int, col_offset: int = 0):
        self.lineno = lineno
        self.col_offset = col_offset
