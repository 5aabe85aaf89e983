"""vctpu-lint self-tests: golden expected-findings per checker (positive
AND negative fixtures), suppression-comment handling, baseline
round-trip, CLI exit codes, and the acceptance-criteria seeded
regressions (a raw VCTPU_* environ read, a bare ``except: pass``
fallback, a ``jnp.sum`` over the tree axis) — each must be caught.

ISSUE 4 tentpole satellite."""

from __future__ import annotations

import json
import textwrap

import pytest

from tools import vctpu_lint as lint
from tools.vctpu_lint import baseline as baseline_mod
from tools.vctpu_lint.__main__ import main as lint_main


def run(src: str, path: str = "variantcalling_tpu/snippet.py",
        select: set[str] | None = None) -> list[lint.Finding]:
    return lint.lint_source(path, textwrap.dedent(src), select)


def codes(src: str, **kw) -> list[str]:
    return [f.code for f in run(src, **kw)]


# ---------------------------------------------------------------------------
# VCT001 raw-environ
# ---------------------------------------------------------------------------


def test_vct001_environ_get_flagged():
    fs = run('''
        import os
        chunk = os.environ.get("VCTPU_STREAM_CHUNK_BYTES", "1024")
        ''')
    assert [f.code for f in fs] == ["VCT001"]
    assert "VCTPU_STREAM_CHUNK_BYTES" in fs[0].message
    assert "knobs" in fs[0].message


def test_vct001_subscript_getenv_membership_flagged():
    src = '''
        import os
        a = os.environ["VCTPU_X"]
        b = os.getenv("VCTPU_Y")
        c = "VCTPU_Z" in os.environ
        '''
    assert codes(src) == ["VCT001", "VCT001", "VCT001"]


def test_vct001_non_vctpu_and_registry_exempt():
    # non-VCTPU env reads are fine anywhere
    assert codes('''
        import os
        os.environ.get("JAX_PLATFORMS")
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/x")
        ''') == []
    # the knob registry itself is the sanctioned reader
    assert codes('''
        import os
        raw = os.environ.get("VCTPU_ENGINE")
        ''', path="variantcalling_tpu/knobs.py") == []


# ---------------------------------------------------------------------------
# VCT002 silent-fallback
# ---------------------------------------------------------------------------


def test_vct002_bare_except_pass_flagged():
    # the acceptance-criteria seeded regression: bare except, swallowed
    fs = run('''
        try:
            score()
        except:
            pass
        ''')
    assert [f.code for f in fs] == ["VCT002"]
    assert "bare except" in fs[0].message


def test_vct002_broad_exception_swallow_flagged():
    assert codes('''
        try:
            build()
        except Exception:
            result = None
        ''') == ["VCT002"]
    # broad type hiding inside a tuple is still broad
    assert codes('''
        try:
            build()
        except (ValueError, Exception):
            result = None
        ''') == ["VCT002"]


def test_vct002_compliant_forms_not_flagged():
    # re-raise (incl. conditional), EngineError, and degrade.record are
    # the three sanctioned outcomes
    assert codes('''
        try:
            build()
        except Exception as e:
            if explicit:
                raise EngineError("no") from e
            log(e)
            raise
        ''') == []
    assert codes('''
        from variantcalling_tpu.utils import degrade
        try:
            probe()
        except Exception as e:
            degrade.record("test.probe", e, fallback="default")
            value = None
        ''') == []
    # narrow excepts are outside VCT002's scope
    assert codes('''
        try:
            open(p)
        except OSError:
            pass
        ''') == []


# ---------------------------------------------------------------------------
# VCT003 unordered-reduction
# ---------------------------------------------------------------------------


def test_vct003_tree_axis_sum_flagged():
    # the acceptance-criteria seeded regression: jnp.sum over tree margins
    fs = run('''
        import jax.numpy as jnp
        def finalize(per_tree):
            return jnp.sum(per_tree, axis=0)
        ''')
    assert [f.code for f in fs] == ["VCT003"]
    assert "sequential_tree_sum" in fs[0].message


def test_vct003_method_sum_and_margin_names_flagged():
    assert codes('''
        def total(tree_margins):
            return tree_margins.sum(axis=0)
        ''') == ["VCT003"]
    assert codes('''
        import jax.numpy as jnp
        m = jnp.sum(margins)
        ''') == ["VCT003"]


def test_vct003_sequential_tree_sum_exempt_and_negatives():
    # the one sanctioned reducer
    assert codes('''
        import jax.numpy as jnp
        def sequential_tree_sum(per_tree):
            import jax
            return per_tree.sum(axis=0)
        ''') == []
    # sums over non-tree data are fine
    assert codes('''
        import jax.numpy as jnp
        depth = jnp.sum(counts, axis=1)
        n = (forest.feature != LEAF).sum(axis=1)
        total = df["n_meth"].sum()
        ''') == []


# ---------------------------------------------------------------------------
# VCT004 tracer host-sync
# ---------------------------------------------------------------------------


def test_vct004_item_float_asarray_in_jit_flagged():
    src = '''
        import jax
        import numpy as np

        @jax.jit
        def bad(x):
            v = x.item()
            f = float(x)
            a = np.asarray(x)
            return v + f
        '''
    assert codes(src) == ["VCT004", "VCT004", "VCT004"]


def test_vct004_partial_jit_and_negatives():
    assert codes('''
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("n",))
        def bad(x, n):
            return x.tolist()
        ''') == ["VCT004"]
    # outside jit: host syncs are fine; inside jit: jnp/constants are fine
    assert codes('''
        import jax
        import jax.numpy as jnp

        def host(x):
            return float(x)

        @jax.jit
        def good(x):
            return jnp.asarray(x) * float(2)
        ''') == []


# ---------------------------------------------------------------------------
# VCT005 unbounded-subprocess
# ---------------------------------------------------------------------------


def test_vct005_run_without_timeout_flagged():
    assert codes('''
        import subprocess
        subprocess.run(["beagle"], capture_output=True)
        ''') == ["VCT005"]
    assert codes('''
        import subprocess
        subprocess.run(["x"], timeout=60)
        ''') == []


def test_vct005_popen_and_thread_rules():
    # Popen with no bounded wait in the function
    assert codes('''
        import subprocess
        def go():
            p = subprocess.Popen(["x"])
            return p.wait()
        ''') == ["VCT005"]
    # bounded communicate makes it compliant
    assert codes('''
        import subprocess
        def go():
            p = subprocess.Popen(["x"])
            out, err = p.communicate(timeout=30)
        ''') == []
    # the non-daemon-thread clause lives SOLELY in VCT010 rule 2 now —
    # one defect must not yield two findings needing two suppression
    # codes, and VCT010 is strictly stricter (a join path does not
    # excuse a non-daemon worker outside parallel/pipeline.py)
    src = '''
        import threading
        t = threading.Thread(target=work)
        t.start()
        '''
    assert codes(src, select={"VCT005"}) == []
    assert codes(src) == ["VCT010"]


# ---------------------------------------------------------------------------
# VCT006 raw-timing
# ---------------------------------------------------------------------------


def test_vct006_raw_wallclock_timing_flagged():
    fs = run('''
        import time
        t0 = time.perf_counter()
        work()
        dt = time.perf_counter() - t0
        stamp = time.time()
        ''')
    assert [f.code for f in fs] == ["VCT006"] * 3
    assert "trace.stage" in fs[0].message
    # bare import form (from time import perf_counter)
    assert codes('''
        from time import perf_counter
        t0 = perf_counter()
        ''') == ["VCT006"]


def test_vct006_aliased_imports_not_an_evasion():
    # `import time as _time` — the exact spelling the executor uses —
    # and renamed from-imports must hit like the canonical form
    assert codes('''
        import time as _time
        t0 = _time.perf_counter()
        ''') == ["VCT006"]
    assert codes('''
        from time import time as now, perf_counter as pc
        a = now()
        b = pc()
        ''') == ["VCT006", "VCT006"]
    # a foreign module that merely shares a clock method name is NOT time
    assert codes('''
        import mylib
        t = mylib.perf_counter()
        ''') == []


def test_vct006_monotonic_sleep_and_nonlibrary_exempt():
    # deadline checks and sleeps are not timing measurements
    assert codes('''
        import time
        deadline = time.monotonic() + 5
        time.sleep(0.1)
        ''') == []
    # only library code is in scope: benchmarks/tools/tests own their stopwatches
    src = '''
        import time
        t0 = time.perf_counter()
        '''
    assert codes(src, path="benchmarks/run_cell.py") == []
    assert codes(src, path="tools/podrun.py") == []
    # the obs subsystem and trace.py ARE the timing layer
    assert codes(src, path="variantcalling_tpu/obs/__init__.py") == []
    assert codes(src, path="variantcalling_tpu/utils/trace.py") == []


def test_vct006_suppression_for_sanctioned_sites():
    # the executor's obs span timing carries a per-line suppression —
    # the same escape hatch every checker honors
    assert codes('''
        import time
        t0 = time.perf_counter()  # vctpu-lint: disable=VCT006 — obs span timing
        ''') == []


# ---------------------------------------------------------------------------
# VCT007 undeclared-event-kind
# ---------------------------------------------------------------------------


def test_vct007_undeclared_kind_flagged():
    fs = run('''
        from variantcalling_tpu import obs
        obs.event("brand_new_kind", "x", value=1)
        ''')
    assert [f.code for f in fs] == ["VCT007"]
    assert "brand_new_kind" in fs[0].message
    assert "event_schema.json" in fs[0].message


def test_vct007_declared_kinds_pass():
    # every committed kind is fine, through both the public emit and the
    # writer-internal _emit spelling
    assert codes('''
        from variantcalling_tpu import obs
        obs.event("heartbeat", "stream", chunks=1, records=2)
        obs.event("profile", "stage", stage="ingest")
        obs.event("journal", "resume_decision", outcome="fresh")
        run._emit("manifest", "tool", {})
        ''') == []


def test_vct007_internal_emit_flagged_and_nonliteral_ignored():
    assert codes('''
        run._emit("mystery", "tool", {})
        ''') == ["VCT007"]
    # non-literal kinds are the schema validator's job, not the linter's
    assert codes('''
        from variantcalling_tpu import obs
        obs.event(kind_var, "x")
        ''') == []


def test_vct007_tests_exempt_and_schema_is_source_of_truth():
    # tests exercise deliberately-bogus kinds
    assert codes('''
        from variantcalling_tpu import obs
        obs.event("bogus", "x")
        ''', path="tests/unit/test_whatever.py") == []
    # the checker reads the COMMITTED artifact: every kind it accepts is
    # a key of event_schema.json
    from tools.vctpu_lint.checkers import UndeclaredEventKindChecker

    kinds = UndeclaredEventKindChecker.schema_kinds()
    assert {"manifest", "span", "profile", "metrics", "run_end"} <= set(kinds)


# ---------------------------------------------------------------------------
# suppression comments, syntax errors, select
# ---------------------------------------------------------------------------


def test_suppression_comment_silences_one_code():
    src = '''
        import os
        x = os.environ.get("VCTPU_X")  # vctpu-lint: disable=VCT001 — test fixture
        y = os.environ.get("VCTPU_Y")
        '''
    fs = run(src)
    assert [(f.code, "VCTPU_Y" in f.message) for f in fs] == [("VCT001", True)]


def test_suppression_all_and_wrong_code():
    assert run('''
        try:
            f()
        except Exception:  # vctpu-lint: disable=all — fixture
            pass
        ''') == []
    # a disable for a DIFFERENT code does not silence the finding
    assert codes('''
        try:
            f()
        except Exception:  # vctpu-lint: disable=VCT001
            pass
        ''') == ["VCT002"]


def test_syntax_error_is_vct000():
    fs = run("def broken(:\n    pass\n")
    assert [f.code for f in fs] == ["VCT000"]


def test_select_runs_only_requested_checkers():
    src = '''
        import os
        x = os.environ.get("VCTPU_X")
        try:
            f()
        except:
            pass
        '''
    assert codes(src, select={"VCT002"}) == ["VCT002"]


# ---------------------------------------------------------------------------
# baseline round-trip + CLI
# ---------------------------------------------------------------------------

_DIRTY = '''import os
x = os.environ.get("VCTPU_X")
try:
    f()
except:
    pass
'''


def test_baseline_round_trip(tmp_path):
    snippet = tmp_path / "dirty.py"
    snippet.write_text(_DIRTY)
    bl = tmp_path / "baseline.json"

    # 1) dirty file with empty baseline -> exit 1, findings printed
    assert lint_main([str(snippet), "--baseline", str(bl)]) == 1

    # 2) write the baseline -> exit 0 afterwards (same findings grandfathered)
    assert lint_main([str(snippet), "--baseline", str(bl),
                      "--write-baseline"]) == 0
    data = json.loads(bl.read_text())
    assert {e["code"] for e in data["entries"]} == {"VCT001", "VCT002"}
    assert all(e["justification"] == "TODO" for e in data["entries"])
    assert lint_main([str(snippet), "--baseline", str(bl)]) == 0

    # 3) a NEW finding is still caught
    snippet.write_text(_DIRTY + 'y = os.environ.get("VCTPU_NEW")\n')
    assert lint_main([str(snippet), "--baseline", str(bl)]) == 1

    # 4) --write-baseline round-trips justifications by fingerprint
    entries = json.loads(bl.read_text())["entries"]
    for e in entries:
        e["justification"] = f"why {e['code']}"
    bl.write_text(json.dumps({"version": 1, "entries": entries}))
    assert lint_main([str(snippet), "--baseline", str(bl),
                      "--write-baseline"]) == 0
    kept = {e["code"]: e["justification"]
            for e in json.loads(bl.read_text())["entries"]}
    assert kept["VCT002"] == "why VCT002"


def test_baseline_fingerprint_survives_line_drift(tmp_path):
    snippet = tmp_path / "drift.py"
    snippet.write_text(_DIRTY)
    bl = tmp_path / "baseline.json"
    assert lint_main([str(snippet), "--baseline", str(bl),
                      "--write-baseline"]) == 0
    # unrelated edit shifts every line; fingerprints (code, path, text) hold
    snippet.write_text("# a new leading comment\n" + _DIRTY)
    assert lint_main([str(snippet), "--baseline", str(bl)]) == 0


def test_cli_unknown_select_is_usage_error(tmp_path):
    assert lint_main(["--select", "VCT999", str(tmp_path)]) == 2


# ---------------------------------------------------------------------------
# VCT008 unsequenced-write
# ---------------------------------------------------------------------------

PIPE = "variantcalling_tpu/pipelines/snippet.py"


def test_vct008_direct_sink_write_flagged():
    fs = run("""
        def commit(sink, data):
            sink.write(data)
        """, path=PIPE)
    assert [f.code for f in fs] == ["VCT008"]
    assert "_sink_write" in fs[0].message


def test_vct008_partial_handle_and_os_replace_flagged():
    assert codes("""
        import os
        def finish(partial_fh, out):
            partial_fh.writelines([b"x"])
            os.replace(out + ".partial", out)
        """, path=PIPE) == ["VCT008", "VCT008"]


def test_vct008_sanctioned_committer_and_other_writers_pass():
    # the committer itself is the sanctioned writer; report/stderr writers
    # and non-sink handles are not streaming output paths
    assert codes("""
        import sys
        def _sink_write(sink, data):
            def attempt():
                sink.write(data)
            attempt()
        def report(fh):
            fh.write("<html>")
            sys.stderr.write("done")
        """, path=PIPE) == []


def test_vct008_scoped_to_pipelines_and_suppressible():
    # io/ writer classes are the sanctioned layer below the committer
    assert codes("""
        def flush(sink, data):
            sink.write(data)
        """, path="variantcalling_tpu/io/bgzf.py") == []
    assert codes("""
        import os
        os.replace(a, b)  # vctpu-lint: disable=VCT008 — sanctioned atomic commit
        """, path=PIPE, select={"VCT008"}) == []


# ---------------------------------------------------------------------------
# VCT009 shardmap-margin-reduction
# ---------------------------------------------------------------------------


def test_vct009_psum_over_margins_in_shard_map_body_flagged():
    fs = run("""
        import jax
        from jax import shard_map

        def body(x, margins):
            return jax.lax.psum(margins, "dp")

        prog = shard_map(body, mesh=None, in_specs=(), out_specs=())
        """)
    assert [f.code for f in fs] == ["VCT009"]
    assert "psum" in fs[0].message
    assert "sequential_tree_sum" in fs[0].message


def test_vct009_jnp_sum_over_scores_in_shard_program_body_flagged():
    # the repo's own wrapper installs shard_map bodies too; score-named
    # arrays are in the vocabulary (the mesh path moves scores around)
    assert codes("""
        import jax.numpy as jnp
        from variantcalling_tpu.parallel import shard_score

        def per_device(score_block):
            return jnp.sum(score_block, axis=0)

        fn = shard_score.shard_program(per_device, mesh, n_data_args=1)
        """) == ["VCT009"]
    # method form (VCT003 also fires on the tree/margin vocabulary —
    # both codes own this line; select isolates the shard_map rule)
    assert codes("""
        from jax import shard_map

        def body(tree_margins):
            return tree_margins.sum(axis=1)

        f = shard_map(body, mesh=m, in_specs=(), out_specs=())
        """, select={"VCT009"}) == ["VCT009"]


def test_vct009_resolves_aliased_bodies():
    # the production install shape (pipelines/filter_variants.py): the
    # body binds through an intermediate name before shard_program —
    # aliases resolve transitively, conditional rebinds scan every source
    assert codes("""
        import jax
        from variantcalling_tpu.parallel import shard_score

        def body(x, margins):
            return jax.lax.psum(margins, "dp")

        def build(mesh, cond):
            if cond:
                fn = body
            else:
                fn = other_body
            fn = fn
            return shard_score.shard_program(fn, mesh, n_data_args=1)
        """, select={"VCT009"}) == ["VCT009"]
    # an aliased lambda body is still a body
    assert codes("""
        import jax
        from jax import shard_map

        fn = lambda margins: jax.lax.psum(margins, "dp")
        prog = shard_map(fn, mesh=None, in_specs=(), out_specs=())
        """, select={"VCT009"}) == ["VCT009"]
    # aliasing alone doesn't widen the net: a never-installed function
    # stays unscanned even when an unrelated alias of it exists
    assert codes("""
        import jax
        from jax import shard_map

        def body(x):
            return x

        def loose(margins):
            return jax.lax.psum(margins, "dp")

        other = loose
        prog = shard_map(body, mesh=None, in_specs=(), out_specs=())
        """, select={"VCT009"}) == []


def test_vct009_sanctioned_and_unrelated_sums_pass():
    # margins merged through the sanctioned site, psum over non-margin
    # data (the SEC cohort counts), and sums OUTSIDE shard_map bodies
    # are all fine (VCT003 owns the outside-world rule)
    assert codes("""
        import jax
        import jax.numpy as jnp
        from jax import shard_map

        def body(x, counts):
            m = sequential_tree_sum(x)
            return m + jax.lax.psum(counts, "dp")

        prog = shard_map(body, mesh=None, in_specs=(), out_specs=())

        def not_a_body(weights):
            return jnp.sum(weights)
        """, select={"VCT009"}) == []
    # a lambda body is still a body
    assert codes("""
        import jax
        from jax import shard_map

        prog = shard_map(lambda margins: jax.lax.psum(margins, "dp"),
                         mesh=None, in_specs=(), out_specs=())
        """, select={"VCT009"}) == ["VCT009"]


def test_vct009_suppressible():
    assert codes("""
        import jax
        from jax import shard_map

        def body(margins):
            return jax.lax.psum(margins, "dp")  # vctpu-lint: disable=VCT009 — test fixture

        prog = shard_map(body, mesh=None, in_specs=(), out_specs=())
        """) == []


def test_cli_list_checkers(capsys):
    assert lint_main(["--list-checkers"]) == 0
    out = capsys.readouterr().out
    for code in ("VCT001", "VCT002", "VCT003", "VCT004", "VCT005", "VCT006",
                 "VCT007", "VCT008", "VCT009", "VCT010"):
        assert code in out


# ---------------------------------------------------------------------------
# the real tree stays clean (the acceptance gate, in-process)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("target", ["variantcalling_tpu", "tools"])
def test_repo_tree_is_clean(target):
    findings = lint.lint_paths([target])
    new, _old, _stale = baseline_mod.partition(
        findings, baseline_mod.load(baseline_mod.DEFAULT_BASELINE))
    assert not new, "new lint findings:\n" + "\n".join(
        f.render() for f in new)


# ---------------------------------------------------------------------------
# VCT010 concurrency-discipline (snippet mode: throwaway one-module index)
# ---------------------------------------------------------------------------


def test_vct010_unlocked_mutation_from_pool_task_flagged():
    fs = run('''
        _CACHE = {}

        def task(x):
            _CACHE[x] = 1

        pool.submit(task, 3)
        ''', select={"VCT010"})
    assert [f.code for f in fs] == ["VCT010"]
    assert "_CACHE" in fs[0].message
    assert "submit" in fs[0].message


def test_vct010_locked_mutation_stays_clean():
    assert codes('''
        import threading

        _CACHE = {}
        _LOCK = threading.Lock()

        def task(x):
            with _LOCK:
                _CACHE[x] = 1

        pool.submit(task, 3)
        ''', select={"VCT010"}) == []


def test_vct010_sanctioned_queue_handoff_stays_clean():
    # handing results across threads through queue.Queue IS the
    # sanctioned pattern — not a race
    assert codes('''
        import queue

        _RESULTS = queue.Queue()

        def task(x):
            _RESULTS.put(x)

        pool.submit(task, 1)
        ''', select={"VCT010"}) == []


def test_vct010_mutation_without_thread_entry_stays_clean():
    # same mutation, never installed as a thread entry: main-thread-only
    # code owns its module state
    assert codes('''
        _CACHE = {}

        def warm(x):
            _CACHE[x] = 1
        ''', select={"VCT010"}) == []


def test_vct010_imap_ordered_task_and_thread_target_are_entries():
    assert codes('''
        _SEEN = []

        def parse(chunk):
            _SEEN.append(chunk)
            return chunk

        out = imap_ordered(pool, parse, chunks)
        ''', select={"VCT010"}) == ["VCT010"]
    assert codes('''
        import threading

        _STATE = {}

        def worker():
            _STATE["k"] = 1

        t = threading.Thread(target=worker, daemon=True)
        ''', select={"VCT010"}) == ["VCT010"]


def test_vct010_stage_pipeline_stage_fn_is_an_entry():
    assert codes('''
        _TALLY = {}

        def render_stage(item):
            _TALLY[item] = 1
            return item

        pipe = StagePipeline([render_stage], source)
        ''', select={"VCT010"}) == ["VCT010"]


def test_vct010_submitted_lambda_mutation_flagged():
    assert codes('''
        _EVENTS = []
        pool.submit(lambda: _EVENTS.append(1))
        ''', select={"VCT010"}) == ["VCT010"]


def test_vct010_lock_order_inversion_flagged():
    fs = run('''
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def ab():
            with A:
                with B:
                    pass

        def ba():
            with B:
                with A:
                    pass
        ''', select={"VCT010"})
    assert [f.code for f in fs] == ["VCT010"]
    assert "lock order" in fs[0].message


def test_vct010_consistent_lock_order_stays_clean():
    assert codes('''
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def f():
            with A:
                with B:
                    pass

        def g():
            with A:
                with B:
                    pass
        ''', select={"VCT010"}) == []


def test_vct010_lock_order_through_call_edge_flagged():
    # one leg of the inversion acquires the inner lock in a CALLEE —
    # only the resolved call graph sees it
    assert codes('''
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def inner_b():
            with B:
                pass

        def ab():
            with A:
                inner_b()

        def ba():
            with B:
                with A:
                    pass
        ''', select={"VCT010"}) == ["VCT010"]


def test_vct010_multi_item_with_acquisition_order():
    # `with A, B:` acquires left-to-right — one With statement's items
    # are ordered exactly like nested With statements
    assert codes('''
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def ab():
            with A, B:
                pass

        def ba():
            with B:
                with A:
                    pass
        ''', select={"VCT010"}) == ["VCT010"]
    assert codes('''
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def ab():
            with A, B:
                pass

        def ba():
            with B, A:
                pass
        ''', select={"VCT010"}) == ["VCT010"]


def test_vct010_lock_order_through_from_import_spelling():
    # `from a import _LOCK` must unify with module a's own identity —
    # a cross-module inversion through the from-import spelling is the
    # same deadlock as the a._LOCK attribute spelling
    fs = [f for f in lint.lint_sources({
        "variantcalling_tpu/la.py": '''
import threading

_LOCK = threading.Lock()
_OTHER_LOCK = threading.Lock()

def fwd():
    with _LOCK:
        with _OTHER_LOCK:
            pass
''',
        "variantcalling_tpu/lb.py": '''
from variantcalling_tpu.la import _LOCK, _OTHER_LOCK

def rev():
    with _OTHER_LOCK:
        with _LOCK:
            pass
''',
    }) if f.code == "VCT010"]
    assert len(fs) == 1 and "lock order" in fs[0].message


def test_vct010_lock_order_through_call_cycle_flagged():
    # the inner acquisition sits on a CALL CYCLE (cyc_g <-> cyc_h) and
    # is first reached from a held call that enters the cycle at cyc_g;
    # a memoized recursive walk cuts the cycle there and caches cyc_h
    # as lock-free, hiding the A->B leg from the later caller_b held
    # call — only a fixpoint over the call graph sees it
    assert codes('''
        import threading

        A = threading.Lock()
        B = threading.Lock()
        X = threading.Lock()

        def caller_a():
            with X:
                cyc_g()

        def caller_b():
            with A:
                cyc_h()

        def cyc_g():
            with B:
                pass
            cyc_h()

        def cyc_h():
            cyc_g()

        def zz_inverse():
            with B:
                with A:
                    pass
        ''', select={"VCT010"}) == ["VCT010"]


def test_vct010_non_daemon_thread_outside_pipeline_flagged():
    src = '''
        import threading

        t = threading.Thread(target=work)
        t.start()
        t.join()
        '''
    fs = run(src, select={"VCT010"})
    assert [f.code for f in fs] == ["VCT010"]
    assert "non-daemon" in fs[0].message
    # the executor module owns the join/watchdog discipline
    assert codes(src, path="variantcalling_tpu/parallel/pipeline.py",
                 select={"VCT010"}) == []
    # daemon workers are fine anywhere
    assert codes('''
        import threading

        t = threading.Thread(target=work, daemon=True)
        t.start()
        ''', select={"VCT010"}) == []


def test_vct010_per_thread_cells_module_exempt():
    assert codes('''
        _CELLS = {}

        def observe(v):
            _CELLS[v] = 1

        pool.submit(observe, 2)
        ''', path="variantcalling_tpu/obs/metrics.py",
        select={"VCT010"}) == []


def test_vct010_suppressible():
    assert codes('''
        _DIAG = {}

        def task(x):
            _DIAG[x] = 1  # vctpu-lint: disable=VCT010 — GIL-atomic diagnostic, last write wins by design

        pool.submit(task, 1)
        ''', select={"VCT010"}) == []


# ---------------------------------------------------------------------------
# project model: whole-program index + cross-module resolution
# ---------------------------------------------------------------------------


def run_sources(sources: dict[str, str],
                select: set[str] | None = None) -> list[lint.Finding]:
    return lint.lint_sources(
        {p: textwrap.dedent(s) for p, s in sources.items()}, select)


def test_project_index_resolves_cross_module_names():
    from tools.vctpu_lint.project import ProjectIndex

    idx = ProjectIndex.build({
        "variantcalling_tpu/a.py": textwrap.dedent('''
            def helper():
                pass
            '''),
        "variantcalling_tpu/b.py": textwrap.dedent('''
            from variantcalling_tpu.a import helper as h

            def caller():
                h()
            '''),
    })
    key = idx.resolve_name("variantcalling_tpu/b.py", "h")
    assert key == ("variantcalling_tpu/a.py", "helper")
    caller = idx.modules["variantcalling_tpu/b.py"].functions["caller"]
    assert key in caller.calls
    assert idx.reaches(("variantcalling_tpu/b.py", "caller"), key)


def test_project_index_registers_thread_entries_and_traced_bodies():
    from tools.vctpu_lint.project import ProjectIndex

    idx = ProjectIndex.build({
        "variantcalling_tpu/work.py": textwrap.dedent('''
            def task(x):
                return x

            def body(x):
                return x
            '''),
        "variantcalling_tpu/pipelines/drive.py": textwrap.dedent('''
            from variantcalling_tpu.work import task, body
            from variantcalling_tpu.parallel import shard_score

            def go(pool, mesh):
                pool.submit(task, 1)
                return shard_score.shard_program(body, mesh, n_data_args=1)
            '''),
    })
    assert ("variantcalling_tpu/work.py", "task") in idx.thread_entries
    assert ("variantcalling_tpu/work.py", "body") in idx.traced_bodies
    assert idx.traced_bodies_in("variantcalling_tpu/work.py") == {"body"}
    assert idx.pipeline_submitted_tasks("variantcalling_tpu/work.py") \
        == {"task"}


def test_vct009_cross_module_alias_body_flagged():
    # the PR-8 incident shape generalized: the shard_map body lives in
    # ONE module, the install site (through a from-import) in ANOTHER —
    # invisible to any per-file view
    fs = run_sources({
        "variantcalling_tpu/bodies.py": '''
            import jax

            def fused_body(x, margins):
                return jax.lax.psum(margins, "dp")
            ''',
        "variantcalling_tpu/install.py": '''
            from variantcalling_tpu.bodies import fused_body
            from variantcalling_tpu.parallel import shard_score

            prog = shard_score.shard_program(fused_body, mesh, n_data_args=1)
            ''',
    }, select={"VCT009"})
    assert [(f.path, f.code) for f in fs] \
        == [("variantcalling_tpu/bodies.py", "VCT009")]
    # per-file view of the body module alone: NOT flagged (no install in
    # sight) — the cross-module finding is the project model's
    assert run('''
        import jax

        def fused_body(x, margins):
            return jax.lax.psum(margins, "dp")
        ''', select={"VCT009"}) == []


def test_vct008_pool_task_sink_write_flagged_outside_pipelines():
    # the whole per-chunk body fans out on the IO pool: a sink write
    # inside such a task is a pipeline write wherever the function lives
    fs = run_sources({
        "variantcalling_tpu/io/helpers.py": '''
            import os

            def commit_task(tmp, out_path):
                os.replace(tmp, out_path)
            ''',
        "variantcalling_tpu/pipelines/some_pipe.py": '''
            from variantcalling_tpu.io.helpers import commit_task

            def run(pool, tmp, out):
                pool.submit(commit_task, tmp, out)
            ''',
    }, select={"VCT008"})
    assert [(f.path, f.code) for f in fs] \
        == [("variantcalling_tpu/io/helpers.py", "VCT008")]
    # the same io-layer write NOT submitted from pipelines stays the
    # sanctioned layer below
    assert run_sources({
        "variantcalling_tpu/io/helpers.py": '''
            import os

            def commit_task(tmp, out_path):
                os.replace(tmp, out_path)
            ''',
    }, select={"VCT008"}) == []


def test_vct002_helper_routed_degrade_is_compliant_with_project():
    sources = {
        "variantcalling_tpu/utils/degrade.py": '''
            def record(point, exc, **kw):
                pass
            ''',
        "variantcalling_tpu/utils/notify.py": '''
            from variantcalling_tpu.utils import degrade

            def note_failure(e):
                degrade.record("worker", e)
            ''',
        "variantcalling_tpu/worker.py": '''
            from variantcalling_tpu.utils.notify import note_failure

            def go():
                try:
                    risky()
                except Exception as e:
                    note_failure(e)
            ''',
    }
    assert run_sources(sources, select={"VCT002"}) == []
    # the per-file view of worker.py alone cannot see through the helper
    assert codes(sources["variantcalling_tpu/worker.py"],
                 path="variantcalling_tpu/worker.py",
                 select={"VCT002"}) == ["VCT002"]
    # a helper that does NOT route to degrade.record stays a finding
    # even with the whole program in view
    bad = dict(sources)
    bad["variantcalling_tpu/utils/notify.py"] = '''
        def note_failure(e):
            print(e)
        '''
    fs = run_sources(bad, select={"VCT002"})
    assert [(f.path, f.code) for f in fs] \
        == [("variantcalling_tpu/worker.py", "VCT002")]


def test_vct010_cross_module_pool_task_mutation_flagged():
    # the ISSUE 9 incident class: state mutated from code reachable ONLY
    # through a pool task submitted in another module
    fs = run_sources({
        "variantcalling_tpu/state.py": '''
            _SHARED = {}

            def poke(k):
                _SHARED[k] = 1
            ''',
        "variantcalling_tpu/pipelines/fanout.py": '''
            from variantcalling_tpu.state import poke

            def run(pool):
                pool.submit(poke, "a")
            ''',
    }, select={"VCT010"})
    assert [(f.path, f.code) for f in fs] \
        == [("variantcalling_tpu/state.py", "VCT010")]


# ---------------------------------------------------------------------------
# CLI: --json, --update-baseline --justify, nonexistent path
# ---------------------------------------------------------------------------


def test_cli_nonexistent_path_is_exit_2(capsys):
    # os.walk on a missing dir yields nothing: before the check this
    # linted ZERO files and passed vacuously
    assert lint_main(["definitely/not/a/path"]) == 2
    assert "does not exist" in capsys.readouterr().err


def test_cli_json_output(tmp_path, capsys):
    snippet = tmp_path / "dirty.py"
    snippet.write_text(_DIRTY)
    bl = tmp_path / "baseline.json"
    assert lint_main([str(snippet), "--baseline", str(bl), "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["new"] == 2 and doc["exit"] == 1
    assert {f["code"] for f in doc["findings"]} == {"VCT001", "VCT002"}
    assert all(f["status"] == "new" for f in doc["findings"])
    # per-checker wall time rides along for every registered checker
    by_code = {c["code"]: c for c in doc["checkers"]}
    assert "VCT010" in by_code
    assert all(c["wall_s"] >= 0 for c in doc["checkers"])
    # clean tree -> exit 0, empty findings, machine-readable all the same
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert lint_main([str(clean), "--baseline", str(bl), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["findings"] == [] and doc["exit"] == 0


def test_cli_update_baseline_requires_justify(tmp_path, capsys):
    snippet = tmp_path / "dirty.py"
    snippet.write_text(_DIRTY)
    bl = tmp_path / "baseline.json"
    assert lint_main([str(snippet), "--baseline", str(bl),
                      "--update-baseline"]) == 2
    assert "--justify" in capsys.readouterr().err
    assert not bl.exists()
    assert lint_main([str(snippet), "--baseline", str(bl),
                      "--update-baseline", "--justify",
                      "fixture debt, tracked in ISSUE-9"]) == 0
    entries = json.loads(bl.read_text())["entries"]
    assert entries and all(e["justification"]
                           == "fixture debt, tracked in ISSUE-9"
                           for e in entries)
    assert lint_main([str(snippet), "--baseline", str(bl)]) == 0


def test_vct010_thread_ctor_import_spellings_flagged():
    # any import spelling counts (the VCT001/VCT004 convention): a
    # from-import or module alias must not evade the non-daemon rule
    assert codes('''
        from threading import Thread

        t = Thread(target=work)
        t.start()
        ''', select={"VCT010"}) == ["VCT010"]
    assert codes('''
        import threading as th

        t = th.Thread(target=work)
        t.start()
        ''', select={"VCT010"}) == ["VCT010"]
    assert codes('''
        from threading import Thread

        t = Thread(target=work, daemon=True)
        t.start()
        ''', select={"VCT010"}) == []


def test_vct010_caller_holds_the_lock_pattern_clean():
    # a helper whose EVERY call site sits inside a lock span is
    # protected by its callers — not a finding
    assert codes('''
        import threading

        _C = {}
        _L = threading.Lock()

        def helper(k):
            _C[k] = 1

        def task(k):
            with _L:
                helper(k)

        pool.submit(task, 1)
        ''', select={"VCT010"}) == []
    # ...but ONE unlocked call site anywhere re-arms the rule
    assert codes('''
        import threading

        _C = {}
        _L = threading.Lock()

        def helper(k):
            _C[k] = 1

        def task(k):
            with _L:
                helper(k)

        def sloppy(k):
            helper(k)

        pool.submit(task, 1)
        ''', select={"VCT010"}) == ["VCT010"]
    # ...and a helper handed to the pool DIRECTLY is an entry — its
    # locked internal call sites do not protect the pool's invocation
    assert codes('''
        import threading

        _C = {}
        _L = threading.Lock()

        def helper(k):
            _C[k] = 1

        def main_path(k):
            with _L:
                helper(k)

        pool.submit(helper, 1)
        ''', select={"VCT010"}) == ["VCT010"]


def test_cli_update_baseline_merges_out_of_scope_entries(tmp_path, capsys):
    # a scoped --update-baseline must not silently delete other files'
    # justified debt from the baseline
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text('import os\nx = os.environ.get("VCTPU_A")\n')
    b.write_text('import os\ny = os.environ.get("VCTPU_B")\n')
    bl = tmp_path / "baseline.json"
    assert lint_main([str(a), str(b), "--baseline", str(bl),
                      "--update-baseline", "--justify", "legacy pair"]) == 0
    capsys.readouterr()
    assert lint_main([str(a), "--baseline", str(bl),
                      "--update-baseline", "--justify", "a only"]) == 0
    entries = json.loads(bl.read_text())["entries"]
    assert len(entries) == 2
    # b.py's entry survived, and a.py kept its ORIGINAL justification
    assert {e["justification"] for e in entries} == {"legacy pair"}
    assert lint_main([str(b), "--baseline", str(bl)]) == 0


def test_cli_update_baseline_replaces_todo_placeholder(tmp_path, capsys):
    # --write-baseline stamps new entries with the TODO placeholder; the
    # sanctioned --update-baseline --justify flow must be able to replace
    # it — TODO is not a human justification, and keeping it silently
    # defeats the policy the flag enforces
    snippet = tmp_path / "dirty.py"
    snippet.write_text(_DIRTY)
    bl = tmp_path / "baseline.json"
    assert lint_main([str(snippet), "--baseline", str(bl),
                      "--write-baseline"]) == 0
    entries = json.loads(bl.read_text())["entries"]
    assert entries and all(e["justification"] == "TODO" for e in entries)
    capsys.readouterr()
    assert lint_main([str(snippet), "--baseline", str(bl),
                      "--update-baseline", "--justify",
                      "real reason at last"]) == 0
    entries = json.loads(bl.read_text())["entries"]
    assert entries and all(e["justification"] == "real reason at last"
                           for e in entries)


def test_vct010_pool_task_via_lambda_wrapper_flagged():
    # pool.submit(lambda: poke(x)) runs poke on a worker exactly like
    # pool.submit(poke, x) — the lambda's CALL TARGETS must enter thread
    # reachability, not just the lambda's own body
    src = '''
        _SHARED = {}

        def poke(k):
            _SHARED[k] = 1

        def main(pool):
            pool.submit(lambda: poke("a"))
        '''
    fs = run(src, select={"VCT010"})
    assert [f.code for f in fs] == ["VCT010"]
    assert "_SHARED" in fs[0].message


def test_vct010_class_level_state_flagged_any_spelling():
    # class-declared attrs live on the class OBJECT — shared across
    # instances and threads whichever spelling the mutation uses
    assert codes('''
        class Stats:
            counts = {}

        def task(k):
            Stats.counts[k] = 1

        pool.submit(task, 1)
        ''', select={"VCT010"}) == ["VCT010"]
    assert codes('''
        import threading

        class Stats:
            counts = {}

            def work(self):
                self.counts["k"] = 1

            def run(self):
                threading.Thread(target=self.work, daemon=True).start()
        ''', select={"VCT010"}) == ["VCT010"]
    # mutator-method spelling on declared class state
    assert codes('''
        class Stats:
            seen = []

        def task(k):
            Stats.seen.append(k)

        pool.submit(task, 1)
        ''', select={"VCT010"}) == ["VCT010"]


def test_vct010_class_state_locked_and_instance_state_clean():
    # holding the lock sanctions the class-state write, and plain
    # per-instance attrs (bound in __init__, usually thread-confined)
    # stay out of scope
    assert codes('''
        import threading

        class Stats:
            counts = {}
            _lock = threading.Lock()

            def __init__(self):
                self.mine = {}

            def work(self):
                with Stats._lock:
                    Stats.counts["k"] = 1
                self.mine["k"] = 1

            def run(self):
                threading.Thread(target=self.work, daemon=True).start()
        ''', select={"VCT010"}) == []


def test_vct010_del_and_tuple_targets_are_mutations():
    # `del _CACHE[x]` is eviction — the same mutation .pop() spells
    # (the _PREDICTOR_CACHE race class) — and unpacking targets hide
    # subscript writes inside a Tuple node
    assert codes('''
        _CACHE = {}

        def task(x):
            del _CACHE[x]

        pool.submit(task, 1)
        ''', select={"VCT010"}) == ["VCT010"]
    assert codes('''
        _A = {}

        def task(k):
            _A[k], x = 1, 2

        pool.submit(task, 1)
        ''', select={"VCT010"}) == ["VCT010"]
    # a LOCAL bound through tuple unpacking is not module state, and a
    # locked del is sanctioned
    assert codes('''
        import threading

        cache = {}
        _L = threading.Lock()

        def task(k):
            cache, x = {}, 1
            cache[k] = 1

        def evict(k):
            with _L:
                del cache[k]

        pool.submit(task, 1)
        pool.submit(evict, 1)
        ''', select={"VCT010"}) == []


def test_vct010_lock_name_needs_word_boundary():
    # "clock"/"blocker" contain the substring "lock" but are NOT locks —
    # a with-block over them must not sanction a shared-state mutation
    assert codes('''
        _C = {}

        def task(k, clk):
            with clk.clock:
                _C[k] = 1

        pool.submit(task, 1, c)
        ''', select={"VCT010"}) == ["VCT010"]
    # every real naming convention still counts as a lock span
    assert codes('''
        import threading

        _C = {}
        _MESH_CACHE_LOCK = threading.Lock()

        def task(k):
            with _MESH_CACHE_LOCK:
                _C[k] = 1

        pool.submit(task, 1)
        ''', select={"VCT010"}) == []


def test_vct010_branch_bound_module_state_and_locks_indexed():
    # module bindings hide in branches exactly like defs do: the
    # native-fallback idiom binds the cache (or the lock guarding it)
    # inside `except ImportError:` — both must be indexed
    assert codes('''
        try:
            from native import cache as _CACHE
        except ImportError:
            _CACHE = {}

        def task(x):
            _CACHE[x] = 1

        pool.submit(task, 1)
        ''', select={"VCT010"}) == ["VCT010"]
    # a lock bound in a branch is a recognized lock (no false positive
    # for the correctly locked mutation; 'MUTEX' has no 'lock' in its
    # spelling so only module_locks registration can sanction it)
    assert codes('''
        import threading

        _C = {}
        try:
            _MUTEX = threading.Lock()
        except Exception:
            _MUTEX = threading.Lock()

        def task(x):
            with _MUTEX:
                _C[x] = 1

        pool.submit(task, 1)
        ''', select={"VCT010"}) == []


def test_cli_update_baseline_reports_merged_entry_count(tmp_path, capsys):
    # the merge path retains out-of-scope entries — the CLI must report
    # the number of entries the baseline now HOLDS, not this run's
    # finding count
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text('import os\nx = os.environ.get("VCTPU_A")\n')
    b.write_text('import os\ny = os.environ.get("VCTPU_B")\n')
    bl = tmp_path / "baseline.json"
    assert lint_main([str(a), str(b), "--baseline", str(bl),
                      "--update-baseline", "--justify", "pair"]) == 0
    capsys.readouterr()
    assert lint_main([str(a), "--baseline", str(bl),
                      "--update-baseline", "--justify", "a only"]) == 0
    out = capsys.readouterr().out
    assert "2 entries" in out and "1 finding(s) from this run" in out
    # --json on the write path emits the structured form
    assert lint_main([str(a), "--baseline", str(bl), "--json",
                      "--update-baseline", "--justify", "a only"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["action"] == "update-baseline"
    assert doc["entries"] == 2 and doc["run_findings"] == 1


def test_vct010_def_in_except_handler_indexed():
    # the repo's own native-fallback idiom defines functions in `except
    # ImportError:` handlers — a def the index cannot see is a def no
    # checker scans, so every branch shape must be walked
    assert codes('''
        _CACHE = {}

        try:
            from native import parse
        except ImportError:
            def parse(x):
                _CACHE[x] = 1

        def main(pool):
            pool.submit(parse, 1)
        ''', select={"VCT010"}) == ["VCT010"]
    # else-branch defs too
    assert codes('''
        _CACHE = {}

        if fast:
            pass
        else:
            def parse(x):
                _CACHE[x] = 1

        pool.submit(parse, 1)
        ''', select={"VCT010"}) == ["VCT010"]


def test_vct010_nested_def_scanned_under_own_key_only():
    # a nested helper whose only call site sits inside a lock span is
    # caller-protected — the enclosing function's scan must not walk
    # into the nested body and re-report it unlocked
    assert codes('''
        import threading

        _C = {}
        _L = threading.Lock()

        def task():
            def inner():
                _C[1] = 2
            with _L:
                inner()

        pool.submit(task)
        ''', select={"VCT010"}) == []
    # ...and the unlocked variant reports exactly ONCE, not once per
    # enclosing scope
    fs = run('''
        import threading

        _C = {}

        def task():
            def inner():
                _C[1] = 2
            inner()

        pool.submit(task)
        ''', select={"VCT010"})
    assert [f.code for f in fs] == ["VCT010"]


def test_vct010_lambda_submit_is_an_unlocked_call_site():
    # an entry lambda's invocation of a helper is an UNLOCKED call site
    # (the pool holds no lock; a lambda body cannot) — it must re-arm
    # the caller-holds-the-lock exemption even when every other call
    # site is lock-protected
    assert codes('''
        import threading

        _C = {}
        _L = threading.Lock()

        def helper(k):
            _C[k] = 1

        def main_path(k):
            with _L:
                helper(k)

        def go(pool):
            pool.submit(lambda: helper(1))
        ''', select={"VCT010"}) == ["VCT010"]
    # ...but a lambda wrapping the LOCKED path stays clean
    assert codes('''
        import threading

        _C = {}
        _L = threading.Lock()

        def helper(k):
            _C[k] = 1

        def main_path(k):
            with _L:
                helper(k)

        def go(pool):
            pool.submit(lambda: main_path(1))
        ''', select={"VCT010"}) == []


def test_vct010_traced_body_lambda_not_a_thread_entry():
    # a jit/shard_map body runs on the MAIN thread — host effects inside
    # it are VCT004's domain, not a thread-reachability finding
    assert codes('''
        import jax

        _STATS = {}

        prog = jax.jit(lambda x: _STATS.setdefault("n", x))
        ''', select={"VCT010"}) == []
