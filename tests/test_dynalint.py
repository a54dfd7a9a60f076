"""dynalint framework + rules, on synthetic snippets, plus the repo gate.

AST-only by design: nothing here imports the engine, jax, or the runtime —
the framework is stdlib-only, so this whole file stays cheap inside the
tight tier-1 budget. Layout:

- framework: suppression scanning, reason-less-suppression meta finding,
  baseline save/load/split/stale, runner wiring on a temp tree;
- one test class per rule, each on purpose-built snippets (positive +
  negative cases);
- the repo gate: ``scripts/dynalint.py`` over the real tree must be clean
  (zero unsuppressed, non-baselined findings — the acceptance criterion).
"""

import importlib.util
import json
import os
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from dynamo_tpu.analysis import baseline as baseline_mod  # noqa: E402
from dynamo_tpu.analysis.core import Finding, Module      # noqa: E402
from dynamo_tpu.analysis.runner import run_lint           # noqa: E402
from dynamo_tpu.analysis.rules.blocking_async import \
    BlockingAsyncRule                                     # noqa: E402
from dynamo_tpu.analysis.rules.fire_forget import \
    FireForgetRule                                        # noqa: E402
from dynamo_tpu.analysis.rules.knob_drift import \
    KnobDriftRule                                         # noqa: E402
from dynamo_tpu.analysis.rules.lock_discipline import \
    LockDisciplineRule                                    # noqa: E402
from dynamo_tpu.analysis.rules.metrics_catalog import \
    catalog_findings, registered_in_module                # noqa: E402
from dynamo_tpu.analysis.rules.silent_except import \
    SilentExceptRule                                      # noqa: E402
from dynamo_tpu.analysis.rules.unbounded_await import \
    UnboundedAwaitRule                                    # noqa: E402


def mod_from(tmp_path, src, name="m.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return Module(str(p), repo=str(tmp_path))


# ---------------------------------------------------------------------------
# framework: suppressions
# ---------------------------------------------------------------------------

def test_suppression_on_line_and_comment_block(tmp_path):
    m = mod_from(tmp_path, """\
        x = 1   # dynalint: ok(some-rule) inline reason
        # a leading comment
        # dynalint: ok(other-rule) block reason
        y = 2
        z = 3
    """)
    assert m.suppressions_at(1) == [("some-rule", "inline reason", 1)]
    assert ("other-rule", "block reason", 3) in m.suppressions_at(4)
    # the comment block does not leak past the statement it precedes
    assert m.suppressions_at(5) == []


def test_reasonless_suppression_raises_meta_finding(tmp_path):
    mod_from(tmp_path, """\
        async def f():
            try:
                pass
            except Exception:   # dynalint: ok(swallowed-exception)
                pass
    """)
    res = run_lint(paths=[str(tmp_path)],
                   rule_names=["swallowed-exception"], repo=str(tmp_path))
    assert res.failed
    assert [f.rule for f in res.findings] == ["suppression"]
    assert "no reason" in res.findings[0].message
    # the same suppression WITH a reason silences everything
    mod_from(tmp_path, """\
        async def f():
            try:
                pass
            except Exception:   # dynalint: ok(swallowed-exception) why not
                pass
    """)
    res = run_lint(paths=[str(tmp_path)],
                   rule_names=["swallowed-exception"], repo=str(tmp_path))
    assert not res.failed and len(res.suppressed) == 1


# ---------------------------------------------------------------------------
# framework: baseline
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_stale(tmp_path):
    f1 = Finding("r", "a.py", 3, "msg", "k1")
    f2 = Finding("r", "a.py", 9, "msg", "k2")
    path = str(tmp_path / "base.json")
    baseline_mod.save(path, [f1, f2])
    base = baseline_mod.load(path)
    assert set(base) == {("r", "a.py", "k1"), ("r", "a.py", "k2")}
    new, old, stale = baseline_mod.split([f1], base)
    assert new == [] and old == [f1]
    assert stale == [("r", "a.py", "k2")]    # k2 fixed -> entry must go
    # a brand-new finding is NOT absorbed
    f3 = Finding("r", "a.py", 5, "msg", "k3")
    new, _old, _ = baseline_mod.split([f1, f3], base)
    assert new == [f3]


def test_baseline_entry_without_reason_rejected(tmp_path):
    path = tmp_path / "base.json"
    path.write_text(json.dumps(
        {"r": [{"path": "a.py", "key": "k", "reason": ""}]}))
    with pytest.raises(ValueError, match="reason"):
        baseline_mod.load(str(path))


def test_runner_grandfathers_then_fails_stale(tmp_path):
    mod_from(tmp_path, """\
        def f():
            try:
                pass
            except Exception:
                pass
    """)
    bp = str(tmp_path / "base.json")
    res = run_lint(paths=[str(tmp_path)],
                   rule_names=["swallowed-exception"], repo=str(tmp_path))
    assert res.failed and len(res.findings) == 1
    baseline_mod.save(bp, res.findings, default_reason="grandfathered")
    res = run_lint(paths=[str(tmp_path)], baseline_path=bp,
                   rule_names=["swallowed-exception"], repo=str(tmp_path))
    assert not res.failed and len(res.grandfathered) == 1
    # fixing the finding makes the baseline entry stale -> run fails again
    mod_from(tmp_path, "def f():\n    pass\n")
    res = run_lint(paths=[str(tmp_path)], baseline_path=bp,
                   rule_names=["swallowed-exception"], repo=str(tmp_path))
    assert res.failed and res.findings == [] and len(res.stale_baseline) == 1


def test_subset_scan_keeps_unscanned_baseline_entries(tmp_path):
    """A narrowed scan must not report baseline entries for files it never
    parsed as stale — only a scan that could reproduce the finding may
    retire its entry."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    silent = ("def g():\n    try:\n        x()\n"
              "    except Exception:\n        pass\n")
    (pkg / "a.py").write_text(silent)
    (pkg / "b.py").write_text(silent.replace("g()", "h()"))
    bp = str(tmp_path / "base.json")
    res = run_lint(paths=[str(pkg)],
                   rule_names=["swallowed-exception"], repo=str(tmp_path))
    assert len(res.findings) == 2
    baseline_mod.save(bp, res.findings, default_reason="grandfathered")
    # scan ONLY a.py: b.py's entry is out of scope, not stale
    res = run_lint(paths=[str(pkg / "a.py")], baseline_path=bp,
                   rule_names=["swallowed-exception"], repo=str(tmp_path))
    assert not res.failed and res.stale_baseline == []
    # full scan with a.py fixed: exactly a.py's entry goes stale
    (pkg / "a.py").write_text("def g():\n    pass\n")
    res = run_lint(paths=[str(pkg)], baseline_path=bp,
                   rule_names=["swallowed-exception"], repo=str(tmp_path))
    assert res.failed and len(res.stale_baseline) == 1
    assert res.stale_baseline[0][1] == "pkg/a.py"


def test_repo_rule_forced_on_subset_sees_full_tree():
    """Forcing knob-drift with a narrowed path set must not misreport
    every knob read outside the subset as a stale registry entry."""
    res = run_lint(paths=[os.path.join(REPO, "dynamo_tpu", "llm")],
                   rule_names=["knob-drift"])
    assert not any(f.key.startswith("stale:") for f in res.findings), \
        [f.key for f in res.findings][:5]


def test_cli_rejects_missing_and_empty_paths(tmp_path, capsys):
    path = os.path.join(REPO, "scripts", "dynalint.py")
    spec = importlib.util.spec_from_file_location("dynalint_cli2", path)
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    with pytest.raises(SystemExit):         # typo'd path: argparse error
        cli.main([str(tmp_path / "no_such_dir")])
    md = tmp_path / "notes.md"
    md.write_text("# not python")
    with pytest.raises(SystemExit):         # existing non-.py file
        cli.main([str(md)])
    empty = tmp_path / "empty"
    empty.mkdir()                           # exists but no .py files
    assert cli.main([str(empty)]) == 2
    # subset --write-baseline would silently drop out-of-subset entries
    py = tmp_path / "ok.py"
    py.write_text("x = 1\n")
    with pytest.raises(SystemExit):
        cli.main([str(py), "--write-baseline"])
    capsys.readouterr()


def test_syntax_error_reported_once_with_repo_rule(tmp_path):
    """A broken file inside a narrowed scan + a forced repo rule (which
    reparses the full default tree) must yield ONE parse finding, not
    two — the file sits under a default root so both passes see it."""
    pkg = tmp_path / "dynamo_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text("def f(:\n")
    res = run_lint(paths=[str(pkg)],
                   rule_names=["swallowed-exception", "metrics-catalog"],
                   repo=str(tmp_path))
    parse = [f for f in res.findings if f.rule == "parse"]
    assert len(parse) == 1 and parse[0].path == "dynamo_tpu/bad.py"


# ---------------------------------------------------------------------------
# rule: blocking-async
# ---------------------------------------------------------------------------

def test_blocking_async_flags_aliased_sleep(tmp_path):
    m = mod_from(tmp_path, """\
        import time as _t
        from subprocess import check_output
        import asyncio

        async def bad():
            _t.sleep(1)
            check_output(["ls"])

        async def good():
            await asyncio.sleep(1)

        def sync_ok():
            _t.sleep(1)
    """)
    found = {(f.key) for f in BlockingAsyncRule().check_module(m)}
    assert found == {"bad:time.sleep", "bad:subprocess.check_output"}


def test_blocking_async_resolves_dotted_imports(tmp_path):
    """``import urllib.request`` binds only ``urllib`` — the resolver must
    canonicalize ``urllib.request.urlopen`` without doubling the submodule
    (regression: it produced 'urllib.request.request.urlopen' and the
    blocking call slipped through)."""
    m = mod_from(tmp_path, """\
        import urllib.request

        async def bad(url):
            urllib.request.urlopen(url)
    """)
    assert [f.key for f in BlockingAsyncRule().check_module(m)] \
        == ["bad:urllib.request.urlopen"]


def test_blocking_async_discriminates_repeat_keys(tmp_path):
    m = mod_from(tmp_path, """\
        import time

        async def f():
            time.sleep(1)
            time.sleep(2)
    """)
    assert [f.key for f in BlockingAsyncRule().check_module(m)] \
        == ["f:time.sleep", "f:time.sleep#2"]


def test_blocking_async_ignores_local_shadows(tmp_path):
    m = mod_from(tmp_path, """\
        async def f():
            async def run():
                return 1
            await run()
    """)
    assert BlockingAsyncRule().check_module(m) == []


# ---------------------------------------------------------------------------
# rule: fire-and-forget
# ---------------------------------------------------------------------------

def test_fire_forget_flags_only_dropped_handles(tmp_path):
    m = mod_from(tmp_path, """\
        import asyncio

        async def bad(loop):
            asyncio.create_task(work())
            asyncio.ensure_future(work())
            loop.create_task(work())

        async def good(loop):
            t = asyncio.create_task(work())
            tasks.append(asyncio.ensure_future(work()))
            asyncio.ensure_future(work()).cancel()
            await asyncio.create_task(work())
            return t
    """)
    fs = FireForgetRule().check_module(m)
    # the second same-shape drop gets a discriminated key: one baseline
    # entry can never grandfather a newly added drop of the same shape
    assert sorted(f.key for f in fs) == [
        "bad:create_task", "bad:create_task#2", "bad:ensure_future"]


def test_fire_forget_resolves_renamed_from_import(tmp_path):
    """`from asyncio import ensure_future as bg; bg(coro)` is the same
    dropped handle under an alias — regression: raw name matching let it
    ship undetected."""
    m = mod_from(tmp_path, """\
        from asyncio import ensure_future as bg

        async def f():
            bg(work())
    """)
    assert [f.key for f in FireForgetRule().check_module(m)] \
        == ["f:ensure_future"]


def test_fire_forget_ignores_unrelated_bare_names(tmp_path):
    m = mod_from(tmp_path, """\
        def create_task(x):
            return x

        def f():
            create_task(1)   # local helper, not asyncio
    """)
    assert FireForgetRule().check_module(m) == []


# ---------------------------------------------------------------------------
# rule: swallowed-exception
# ---------------------------------------------------------------------------

def test_silent_except_positive_and_negative(tmp_path):
    m = mod_from(tmp_path, """\
        import logging
        log = logging.getLogger(__name__)

        def silent():
            try:
                x()
            except Exception:
                pass

        def bare_silent():
            try:
                x()
            except:
                pass

        def narrow_ok():
            try:
                x()
            except ValueError:
                pass

        def logged():
            try:
                x()
            except Exception:
                log.warning("boom", exc_info=True)

        def reraised():
            try:
                x()
            except Exception:
                raise

        def uses_bound():
            try:
                x()
            except Exception as e:
                last_error = str(e)

        def counted(c):
            try:
                x()
            except Exception:
                c.inc()

        def legacy_noqa():
            try:
                x()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
    """)
    fs = SilentExceptRule().check_module(m)
    assert sorted(f.key for f in fs) == ["bare_silent:bare",
                                         "silent:Exception"]


def test_silent_except_nested_def_does_not_count(tmp_path):
    # a handler that only DEFINES a logging closure never runs it
    m = mod_from(tmp_path, """\
        def f():
            try:
                x()
            except Exception:
                def later():
                    log.warning("never called here")
    """)
    assert len(SilentExceptRule().check_module(m)) == 1


# ---------------------------------------------------------------------------
# rule: lock-discipline
# ---------------------------------------------------------------------------

def test_lock_discipline_flags_unguarded_write(tmp_path):
    m = mod_from(tmp_path, """\
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0          # constructor writes are exempt

            def inc(self):
                with self._lock:
                    self.n += 1

            def reset(self):
                self.n = 0          # RACE: guarded attr, no lock

        class Unrelated:
            def set(self):
                self.n = 5          # different class: not guarded here
    """)
    fs = LockDisciplineRule().check_module(m)
    assert [f.key for f in fs] == ["Counter.n@reset"]
    assert fs[0].line == 13


def test_lock_discipline_closure_write_is_unguarded(tmp_path):
    m = mod_from(tmp_path, """\
        class C:
            def locked(self):
                with self._lock:
                    self.v = 1
                    def cb():
                        self.v = 2   # runs later, lock long gone
                    return cb
    """)
    assert [f.key for f in LockDisciplineRule().check_module(m)] \
        == ["C.v@locked"]


def test_lock_discipline_clean_class_passes(tmp_path):
    m = mod_from(tmp_path, """\
        class C:
            def __init__(self):
                self.v = 0

            def a(self):
                with self._state_lock:
                    self.v = 1

            def b(self):
                with self._state_lock:
                    self.v += 2
    """)
    assert LockDisciplineRule().check_module(m) == []


# ---------------------------------------------------------------------------
# rule: unbounded-await (legacy gate, re-homed)
# ---------------------------------------------------------------------------

def test_unbounded_await_synthetic(tmp_path):
    m = mod_from(tmp_path, """\
        import asyncio

        async def bad(reader):
            data = await reader.readexactly(4)

        async def guarded(reader):
            data = await asyncio.wait_for(reader.readexactly(4), 5)

        async def annotated(reader):
            data = await reader.read(4)   # unbounded-ok: rx loop lifetime
    """)
    fs = UnboundedAwaitRule().check_module(m)
    assert [f.key for f in fs] == ["bad:readexactly"]


def test_unbounded_await_scope_pins_legacy_paths():
    scope = UnboundedAwaitRule.scope
    assert "dynamo_tpu/runtime" in scope
    assert "dynamo_tpu/planner" in scope
    assert "dynamo_tpu/utils/overload.py" in scope


# ---------------------------------------------------------------------------
# rule: knob-drift
# ---------------------------------------------------------------------------

def test_knob_drift_unregistered_literal(tmp_path):
    m = mod_from(tmp_path, """\
        import os
        a = os.environ.get("DYN_LEASE_TTL", "10")      # registered
        b = os.environ.get("DYN_TOTALLY_BOGUS", "")    # not registered
        doc = "prose mentioning DYN_ families is ignored"
        prefix = "DYN_PLANNER_"                        # fragment ignored
    """)
    fs = KnobDriftRule().check_repo([m], REPO)
    bogus = [f for f in fs if "BOGUS" in f.key]
    assert len(bogus) == 1 and bogus[0].key == "unregistered:DYN_TOTALLY_BOGUS"
    assert not any("DYN_LEASE_TTL" in f.key and "unregistered" in f.key
                   for f in fs)


def test_knob_registry_covers_repo_and_docs_in_sync():
    """The acceptance criterion: 60+ knobs, all read, docs generated."""
    from dynamo_tpu.utils.knobs import KNOBS, render_markdown
    assert len(KNOBS) >= 60
    with open(os.path.join(REPO, "docs", "configuration.md")) as f:
        assert f.read() == render_markdown()


# ---------------------------------------------------------------------------
# rule: metrics-catalog (legacy gate, re-homed)
# ---------------------------------------------------------------------------

def test_metrics_catalog_synthetic(tmp_path):
    m = mod_from(tmp_path, """\
        reg.counter("dyn_things_total", "help")
        g = registry.gauge
        g("llm_stuff_bytes", "help")
        reg.histogram(dynamic_name, "not a literal: ignored")
    """)
    registered = registered_in_module(m)
    assert set(registered) == {"dyn_things_total", "llm_stuff_bytes"}
    fs = catalog_findings(registered, {"dyn_things_total", "dyn_ghost"})
    assert sorted(f.key for f in fs) == ["stale:dyn_ghost",
                                         "undocumented:llm_stuff_bytes"]


# ---------------------------------------------------------------------------
# the repo gate
# ---------------------------------------------------------------------------

def test_repo_is_dynalint_clean(capsys):
    """Zero unsuppressed, non-baselined findings over dynamo_tpu/ +
    scripts/ — through the real entrypoint, baseline file included."""
    path = os.path.join(REPO, "scripts", "dynalint.py")
    spec = importlib.util.spec_from_file_location("dynalint_cli", path)
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    rc = cli.main([])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "ok:" in out


# ---------------------------------------------------------------------------
# dataflow layer: def-use chains + device taint
# ---------------------------------------------------------------------------

def test_scope_bindings_and_class_attr_bindings(tmp_path):
    import ast

    from dynamo_tpu.analysis.dataflow import (class_attr_bindings,
                                              scope_bindings)
    m = mod_from(tmp_path, """\
        class C:
            def __init__(self, ns):
                self.prefix = make_prefix(ns)

            def go(self):
                key = self.prefix + "x"
                for item in fetch(key):
                    use(item)
                if (n := cost()) > 2:
                    pass
    """)
    cls = next(n for n in ast.walk(m.tree) if isinstance(n, ast.ClassDef))
    attrs = class_attr_bindings(cls)
    assert "prefix" in attrs and len(attrs["prefix"]) == 1
    go = next(n for n in ast.walk(m.tree)
              if isinstance(n, ast.FunctionDef) and n.name == "go")
    b = scope_bindings(go)
    assert set(b) == {"key", "item", "n"}
    assert b["item"][0][1] == "for"     # loop binding tagged as such


def test_device_taint_seeds_and_summaries(tmp_path):
    import ast

    from dynamo_tpu.analysis.dataflow import (DEVBOX, DEVICE, JITFN,
                                              DeviceTaint)
    m = mod_from(tmp_path, """\
        import jax
        import jax.numpy as jnp
        import numpy as np

        class E:
            def __init__(self):
                self._fn = jax.jit(lambda x: x + 1)
                self.k_pool = jax.jit(lambda: jnp.zeros((4,)))()

            def _run(self, x):
                return self._fn(x)

            def stage(self):
                packed = self._run(np.zeros(4))
                self._inflight.append({"packed": packed})

            def fetch(self):
                rec = self._inflight.popleft()
                return np.asarray(rec["packed"])
    """)
    t = DeviceTaint(m)
    assert t.attr_tags["_fn"] == JITFN
    assert t.attr_tags["k_pool"] == DEVICE
    assert t.summaries["_run"] == DEVICE     # jitted-call result flows out
    assert t.attr_tags["_inflight"] == DEVBOX
    fetch = next(n for n in ast.walk(m.tree)
                 if isinstance(n, ast.FunctionDef) and n.name == "fetch")
    hits = t.sink_hits(fetch, "E.fetch")
    assert [h.label for h in hits] == ["np.asarray"]


# ---------------------------------------------------------------------------
# rule: host-sync
# ---------------------------------------------------------------------------

def test_host_sync_positive_and_negative(tmp_path):
    from dynamo_tpu.analysis.rules.host_sync import HostSyncRule
    m = mod_from(tmp_path, """\
        import jax
        import jax.numpy as jnp
        import numpy as np

        step = jax.jit(lambda x: x * 2)

        def bad(x):
            out = step(x)
            t = int(out[0])             # sync: jitted-call result
            arr = np.asarray(out)       # sync: wholesale fetch
            jnp.ones(3).tolist()        # sync: jnp constructor
            return t, arr

        def fine(host_list):
            a = np.asarray(host_list)   # host data: no device involved
            n = int(a[0])
            jnp.asarray(a)              # host->device upload, not a sync
            return n

        def metadata(x):
            out = step(x)
            return out.shape, out.dtype  # host metadata, no transfer
    """)
    fs = HostSyncRule().check_module(m)
    keys = [f.key for f in fs]
    assert "bad:int()" in keys and "bad:np.asarray" in keys \
        and "bad:.tolist()" in keys
    assert not any(k.startswith(("fine:", "metadata:")) for k in keys)


def test_host_sync_container_truthiness_not_flagged(tmp_path):
    """bool()/len() of a container holding device arrays reads host
    metadata; popping an element out and converting it is the sync."""
    from dynamo_tpu.analysis.rules.host_sync import HostSyncRule
    m = mod_from(tmp_path, """\
        import jax, collections
        import numpy as np

        class E:
            def __init__(self):
                self._q = collections.deque()
                self._fn = jax.jit(lambda: 0)

            def push(self):
                self._q.append({"packed": self._fn()})

            def busy(self):
                return bool(self._q)          # len check: fine

            def pop(self):
                rec = self._q.popleft()
                return np.asarray(rec["packed"])   # the actual sync
    """)
    keys = [f.key for f in HostSyncRule().check_module(m)]
    assert keys == ["E.pop:np.asarray"]


def test_host_sync_report_cli_is_complete_transfer_budget(capsys):
    """The acceptance criterion: `--report host-sync` inventories every
    device->host transfer on the dispatch paths with zero OPEN sites —
    each one fixed or carrying a reasoned suppression."""
    path = os.path.join(REPO, "scripts", "dynalint.py")
    spec = importlib.util.spec_from_file_location("dynalint_cli3", path)
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    assert cli.main(["--report", "host-sync"]) == 0
    out = capsys.readouterr().out
    assert "0 open" in out
    # the three dispatch-path fetches are present, each with its reason
    for token in ("_fetch_prefill", "_fetch_decode",
                  "_spec_round", "extract_kv"):
        assert token in out, f"missing {token} in transfer inventory"
    assert out.count("suppressed") >= 8


# ---------------------------------------------------------------------------
# rule: tracer-leak
# ---------------------------------------------------------------------------

def test_tracer_leak_positive_and_negative(tmp_path):
    from dynamo_tpu.analysis.rules.tracer_leak import TracerLeakRule
    m = mod_from(tmp_path, """\
        import jax
        from functools import partial

        COUNT = 0

        @partial(jax.jit, donate_argnums=(0,))
        def bad(x, obj):
            global COUNT
            COUNT = 1          # global write from trace
            helper.cache = x   # closed-over object attr
            return x

        @jax.jit
        def ok(x):
            y = x + 1          # locals are fine
            acc = {}
            acc["k"] = y       # subscript on a LOCAL container is fine

            def body(carry, _):
                carry = carry + y    # nested def, pure
                return carry, None
            return y

        def host(x):
            host.cache = x     # not traced: no finding
            return x
    """)
    keys = [f.key for f in TracerLeakRule().check_module(m)]
    assert "bad:global COUNT" in keys
    assert "bad:helper.cache" in keys
    assert not any(k.startswith(("ok:", "host:")) for k in keys)


def test_tracer_leak_nonlocal_scoping(tmp_path):
    from dynamo_tpu.analysis.rules.tracer_leak import TracerLeakRule
    m = mod_from(tmp_path, """\
        import jax

        def outer():
            leaked = 0

            @jax.jit
            def traced(x):
                inner_acc = 0

                def nested():
                    nonlocal inner_acc     # binds INSIDE the trace: fine
                    inner_acc = 1
                nonlocal leaked            # escapes the trace: flagged
                leaked = 1
                return x
            return traced
    """)
    keys = [f.key for f in TracerLeakRule().check_module(m)]
    assert keys == ["outer.traced:nonlocal leaked"]


# ---------------------------------------------------------------------------
# rule: recompile-hazard
# ---------------------------------------------------------------------------

def test_recompile_hazard_config_args(tmp_path):
    from dynamo_tpu.analysis.rules.recompile_hazard import \
        RecompileHazardRule
    m = mod_from(tmp_path, """\
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def good(x, cfg):
            return x

        @jax.jit
        def bad(x, cfg, attn_impl):
            return x

        @jax.jit
        def clean(x, y):
            return x + y
    """)
    keys = sorted(f.key for f in RecompileHazardRule().check_module(m))
    assert keys == ["bad:config-arg:attn_impl", "bad:config-arg:cfg"]


def test_recompile_hazard_unbucketed_lengths(tmp_path):
    from dynamo_tpu.analysis.rules.recompile_hazard import \
        RecompileHazardRule
    m = mod_from(tmp_path, """\
        import jax
        import numpy as np
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def prog(x, n):
            return x

        def _bucket(n, buckets):
            return buckets[-1]

        def bad(work):
            n = len(work)
            tokens = np.zeros((n, 8), np.int32)   # per-request shape
            return prog(tokens, 4)

        def bad_static(work, x):
            return prog(x, len(work))             # raw len in static slot

        def good(work, x):
            B = _bucket(len(work), [1, 2, 4])
            tokens = np.zeros((B, 8), np.int32)
            return prog(tokens, 4)
    """)
    keys = sorted(f.key for f in RecompileHazardRule().check_module(m))
    assert any(k.startswith("bad:prog:array") for k in keys)
    assert any(k.startswith("bad_static:prog:unbucketed") for k in keys)
    assert not any(k.startswith("good:") for k in keys)


# ---------------------------------------------------------------------------
# rule: await-holding-lock
# ---------------------------------------------------------------------------

def test_await_holding_lock_positive_and_negative(tmp_path):
    from dynamo_tpu.analysis.rules.await_lock import AwaitHoldingLockRule
    m = mod_from(tmp_path, """\
        import asyncio

        class Conn:
            async def bad(self, w, obj):
                async with self._send_lock:
                    await write_frame(w, obj)

            async def fine(self, w, obj):
                async with self._send_lock:
                    self.seq += 1          # bookkeeping under the lock
                await write_frame(w, obj)  # network wait outside

            async def local_ok(self):
                async with self._state_lock:
                    await asyncio.sleep(0)  # not a network call

            async def defer_ok(self, w):
                async with self._send_lock:
                    async def later():
                        await w.drain()     # runs after the lock is gone
                    return later
    """)
    keys = [f.key for f in AwaitHoldingLockRule().check_module(m)]
    assert keys == ["bad:write_frame"]


def test_await_holding_lock_send_lock_sites_audited():
    """The three _send_lock sites are serialization-by-design: each must
    carry a reasoned suppression (audit pinned, not silently muted)."""
    res = run_lint(paths=[
        os.path.join(REPO, "dynamo_tpu", "runtime", "store_client.py"),
        os.path.join(REPO, "dynamo_tpu", "runtime", "store_server.py")],
        rule_names=["await-holding-lock"])
    assert not res.failed
    assert len(res.suppressed) == 3
    assert all(reason for _f, reason in res.suppressed)


# ---------------------------------------------------------------------------
# rule: store-key-drift
# ---------------------------------------------------------------------------

def test_store_key_resolver_chases_fstrings_and_helpers(tmp_path):
    import ast

    from dynamo_tpu.analysis.rules.store_key_drift import _Resolver
    from dynamo_tpu.runtime import keyspace
    m = mod_from(tmp_path, """\
        from dynamo_tpu.planner.loop import decisions_prefix
        from dynamo_tpu.llm.remote import MODEL_PREFIX

        class T:
            def __init__(self, ns):
                self.prefix = decisions_prefix(ns)

            async def a(self, store, ns):
                await store.get_prefix(decisions_prefix(ns))     # helper
            async def b(self, store):
                await store.get_prefix(MODEL_PREFIX)             # constant
            async def c(self, store, tid):
                await store.put(f"traces/{tid}/x", b"")          # literal
            async def d(self, store):
                await store.get_prefix(self.prefix)              # self attr
            async def e(self, store):
                for k, _v in await store.get_prefix(self.prefix):
                    await store.delete(k)                        # store key
            async def f(self, store, thing):
                await store.put(thing.whatever(), b"")           # opaque
    """)
    r = _Resolver(m, keyspace)
    got = {}
    for node in ast.walk(m.tree):
        if isinstance(node, ast.AsyncFunctionDef):
            for call in ast.walk(node):
                if isinstance(call, ast.Call) and isinstance(
                        call.func, ast.Attribute) \
                        and call.func.attr in ("get_prefix", "put",
                                               "delete"):
                    got.setdefault(node.name, r.resolve(
                        call.args[0], node))
    assert got["a"] == ("family", "planner")
    assert got["b"] == ("family", "models")
    assert got["c"] == ("literal", "traces/")
    assert got["d"] == ("family", "planner")
    assert got["e"] == ("family", "planner")
    assert got["f"] is None


def test_store_key_drift_flags_unregistered_and_unresolved(tmp_path):
    from dynamo_tpu.analysis.rules.store_key_drift import StoreKeyDriftRule
    pkg = tmp_path / "dynamo_tpu"
    pkg.mkdir()
    (pkg / "x.py").write_text(textwrap.dedent("""\
        async def rogue(store, ns):
            await store.put(f"shadow/{ns}/state", b"")    # unregistered
        async def opaque(store, blob):
            await store.put(blob.mystery(), b"")          # unresolvable
    """))
    m = Module(str(pkg / "x.py"), repo=str(tmp_path))
    fs = StoreKeyDriftRule().check_repo([m], str(tmp_path))
    keys = {f.key for f in fs if f.path == "dynamo_tpu/x.py"}
    assert keys == {"rogue:put", "opaque:put"}
    # every registered family is unused in this one-file tree -> stale
    assert any(f.key.startswith("stale:") for f in fs)
    assert any(f.key == "doc:missing" for f in fs)


def test_keyspace_registry_covers_repo_and_doc_in_sync():
    """Acceptance: the registry resolves every store call site in the
    tree (no new findings), every family is used, and docs/keyspace.md
    regenerates byte-identical."""
    from dynamo_tpu.runtime import keyspace
    res = run_lint(rule_names=["store-key-drift"])
    assert not res.failed, res.to_text()
    with open(os.path.join(REPO, "docs", "keyspace.md")) as f:
        assert f.read() == keyspace.render_markdown()
    assert len(keyspace.KEYSPACE) >= 12
    # helper/constant indexes are unambiguous
    assert len(keyspace.HELPER_INDEX) == sum(
        len(f.helpers) for f in keyspace.KEYSPACE.values())


# ---------------------------------------------------------------------------
# rule: wire-field-drift
# ---------------------------------------------------------------------------

def _mini_wire_tree(tmp_path, component_src):
    pkg = tmp_path / "dynamo_tpu" / "runtime"
    pkg.mkdir(parents=True)
    (pkg / "wire.py").write_text(textwrap.dedent("""\
        KIND_KEY = "kind"
        MESSAGE_KEY = "message"
        TRACE_KEY = "trace"
        WIRE_FIELDS = {
            "kind": "frame discriminator",
            "message": "error text",
            "trace": "span context",
        }
    """))
    (pkg / "component.py").write_text(textwrap.dedent(component_src))
    return [Module(str(pkg / "wire.py"), repo=str(tmp_path)),
            Module(str(pkg / "component.py"), repo=str(tmp_path))]


def test_wire_field_drift_flags_literals_and_stale(tmp_path):
    from dynamo_tpu.analysis.rules.wire_field_drift import \
        WireFieldDriftRule
    mods = _mini_wire_tree(tmp_path, """\
        from .wire import KIND_KEY, MESSAGE_KEY

        def f(control, send):
            k = control.get("kind")              # literal .get
            send({"kind": "error",               # literal dict keys
                  "mystery": 1}, None)
            ok = {KIND_KEY: "data"}              # constants: fine
            return control.get(KIND_KEY), ok
    """)
    fs = WireFieldDriftRule().check_repo(mods, str(tmp_path))
    keys = sorted(f.key for f in fs)
    assert "literal:kind" in keys            # .get("kind")
    assert "literal:kind#2" in keys          # dict literal
    assert "literal:mystery" in keys         # unregistered field
    assert "stale:TRACE_KEY" in keys         # constant nobody reads
    assert not any("MESSAGE_KEY" in k for k in keys)


def test_wire_field_drift_clean_tree_passes(tmp_path):
    from dynamo_tpu.analysis.rules.wire_field_drift import \
        WireFieldDriftRule
    mods = _mini_wire_tree(tmp_path, """\
        from .wire import KIND_KEY, MESSAGE_KEY, TRACE_KEY

        def f(control, send):
            send({KIND_KEY: "error", MESSAGE_KEY: "x",
                  TRACE_KEY: None}, None)
            return control.get(KIND_KEY)
    """)
    fs = WireFieldDriftRule().check_repo(mods, str(tmp_path))
    # doc-missing findings don't apply to the mini tree (no docs dir)
    assert [f for f in fs if not f.key.startswith("doc-missing:")] == []


def test_wire_registry_real_tree_constants_cover_fields():
    from dynamo_tpu.analysis.rules.wire_field_drift import load_registry
    m = Module(os.path.join(REPO, "dynamo_tpu", "runtime", "wire.py"))
    reg = load_registry([m])
    assert set(reg["fields"]) == set(reg["constants"].values())
    for name in ("context_id", "trace", "priority", "deadline", "stage",
                 "reason", "retry_after"):
        assert name in reg["fields"]
    res = run_lint(rule_names=["wire-field-drift"])
    assert not res.failed, res.to_text()


# ---------------------------------------------------------------------------
# framework: suppression reason continuation, --changed, CI gates
# ---------------------------------------------------------------------------

def test_suppression_reason_continues_across_comment_block(tmp_path):
    m = mod_from(tmp_path, """\
        # dynalint: ok(some-rule) first line of the
        # reason continues here
        x = 1
    """)
    (rule, reason, line) = m.suppressions_at(3)[0]
    assert rule == "some-rule"
    assert reason == "first line of the reason continues here"


def test_changed_mode_scopes_per_file_keeps_repo_rules(tmp_path, capsys,
                                                       monkeypatch):
    path = os.path.join(REPO, "scripts", "dynalint.py")
    spec = importlib.util.spec_from_file_location("dynalint_cli4", path)
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    # --changed and explicit paths are mutually exclusive
    with pytest.raises(SystemExit):
        cli.main(["--changed", "dynamo_tpu/llm"])
    capsys.readouterr()
    # no git changes -> instant clean exit
    monkeypatch.setattr(cli, "changed_files", lambda: [])
    assert cli.main(["--changed"]) == 0
    assert "no changed Python files" in capsys.readouterr().out
    # a changed-file subset still runs the whole-repo drift rules
    target = os.path.join(REPO, "dynamo_tpu", "utils", "overload.py")
    monkeypatch.setattr(cli, "changed_files", lambda: [target])
    assert cli.main(["--changed"]) == 0
    out = capsys.readouterr().out
    assert "15 rules" in out


def test_full_tree_wall_time_within_budget_all_rules_registered():
    """CI gate for the tentpole's cost contract: the whole suite —
    dataflow taint included — stays AST-only and finishes well inside
    10s on the full tree, with all six new rules registered and run."""
    res = run_lint()
    assert not res.failed, res.to_text()
    assert res.elapsed_s < 10.0, f"dynalint took {res.elapsed_s:.1f}s"
    for rule in ("host-sync", "recompile-hazard", "tracer-leak",
                 "store-key-drift", "wire-field-drift",
                 "await-holding-lock", "loop-blocking-path"):
        assert rule in res.rules_run
    assert len(res.rules_run) == 15


def test_host_sync_statement_level_closure_scanned(tmp_path):
    """Regression: a closure defined directly at the statement level of a
    function body is its own scope — its syncs are found, and it is NOT
    scanned under the enclosing env (review finding)."""
    from dynamo_tpu.analysis.rules.host_sync import HostSyncRule
    m = mod_from(tmp_path, """\
        import jax
        import numpy as np

        step = jax.jit(lambda x: x)

        def outer(x):
            def inner():
                out = step(x)
                return np.asarray(out)     # sync inside the closure
            return inner

        def shadowed(x):
            out = step(x)                  # device in the OUTER scope
            def inner(out):
                return np.asarray(out)     # param shadows: unknown host
            return inner, int(out[0])      # the outer sync IS flagged
    """)
    keys = sorted(f.key for f in HostSyncRule().check_module(m))
    assert "outer:np.asarray" in keys
    assert "shadowed:int()" in keys
    assert "shadowed:np.asarray" not in keys


def test_wire_field_drift_flags_subscript_typo(tmp_path):
    """Regression: a typo'd field WRITTEN via subscript on a control dict
    must be flagged as unregistered (review finding)."""
    from dynamo_tpu.analysis.rules.wire_field_drift import \
        WireFieldDriftRule
    mods = _mini_wire_tree(tmp_path, """\
        from .wire import KIND_KEY, MESSAGE_KEY, TRACE_KEY

        def f(base_control, control, send):
            base_control["prority"] = "batch"    # typo: silent fork
            send({KIND_KEY: "error", MESSAGE_KEY: "x",
                  TRACE_KEY: None}, None)
            return control.get(KIND_KEY)
    """)
    fs = WireFieldDriftRule().check_repo(mods, str(tmp_path))
    assert any(f.key == "literal:prority" and "not a registered" in
               f.message for f in fs)


def test_tracer_leak_no_duplicate_findings_in_compound_bodies(tmp_path):
    """Regression: a leak inside a nested def under an `if` must be
    reported exactly once (review finding: ast.walk re-scanned nested
    bodies under the outer frame)."""
    from dynamo_tpu.analysis.rules.tracer_leak import TracerLeakRule
    m = mod_from(tmp_path, """\
        import jax

        @jax.jit
        def step(x, flag):
            if flag:
                def inner(c):
                    helper.cache = c
                    return c
            return x
    """)
    keys = [f.key for f in TracerLeakRule().check_module(m)]
    assert keys == ["step:helper.cache"]


def test_recompile_hazard_in_closures(tmp_path):
    """Regression: the unbucketed-length check covers nested function
    bodies too (review finding)."""
    from dynamo_tpu.analysis.rules.recompile_hazard import \
        RecompileHazardRule
    m = mod_from(tmp_path, """\
        import jax
        import numpy as np

        fn = jax.jit(lambda x: x)

        def outer(batch):
            def helper():
                n = len(batch)
                return fn(np.zeros((n, 4), np.int32))
            return helper
    """)
    keys = [f.key for f in RecompileHazardRule().check_module(m)]
    assert any(k.startswith("outer.helper:fn:array") for k in keys)


def test_wire_field_drift_spread_and_assigned_control_dicts(tmp_path):
    """Regression: dicts built by spreading a control dict, or assigned
    to a control-named variable, are gated without a 'kind' key."""
    from dynamo_tpu.analysis.rules.wire_field_drift import \
        WireFieldDriftRule
    mods = _mini_wire_tree(tmp_path, """\
        from .wire import KIND_KEY, MESSAGE_KEY, TRACE_KEY

        def f(base_control, control, send, endpoint):
            req_control = {**base_control, "endpiont": endpoint}  # typo
            base_control = {TRACE_KEY: None, "message": "x"}
            send(req_control, None)
            return control.get(KIND_KEY), MESSAGE_KEY
    """)
    keys = sorted(f.key for f in WireFieldDriftRule().check_repo(
        mods, str(tmp_path)))
    assert "literal:endpiont" in keys     # spread-built control dict
    assert "literal:message" in keys      # assigned to control name


def test_store_key_drift_doc_check_without_wire_import(tmp_path,
                                                       monkeypatch):
    """Regression: the docs compare must not import wire.py (and thus
    msgpack) at lint time — it feeds the AST-extracted field table into
    render_markdown instead (review finding)."""
    import builtins

    from dynamo_tpu.analysis.rules.store_key_drift import StoreKeyDriftRule
    real_import = builtins.__import__

    def deny_msgpack(name, *a, **kw):
        assert name != "msgpack", "lint-time msgpack import"
        return real_import(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", deny_msgpack)
    monkeypatch.delitem(sys.modules, "msgpack", raising=False)
    monkeypatch.delitem(sys.modules, "dynamo_tpu.runtime.wire",
                        raising=False)
    wire_mod = Module(os.path.join(REPO, "dynamo_tpu", "runtime",
                                   "wire.py"))
    fs = StoreKeyDriftRule().check_repo([wire_mod], REPO)
    # the doc compare RAN (no doc:drift on the real, regenerated doc)
    assert not any(f.key == "doc:drift" for f in fs)
