"""Tests for the project-invariant analyzer (distar_tpu/analysis/).

Per-rule fixture snippets (positive hit, negative clean, pragma-suppressed),
baseline round-trip with shrink-only enforcement, the lockwatch dynamic
sanitizer (a REAL ABBA order cycle across two threads), and the tier-1 gate:
``test_analysis_repo_clean`` runs the full analyzer over the committed tree
and fails on any non-baselined finding (the lint-from-tests idiom).
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from distar_tpu.analysis import (  # noqa: E402
    Analyzer,
    apply_baseline,
    collect_files,
    load_baseline,
    render_markdown,
    save_baseline,
)


def run_on(tmp_path, source, filename="distar_tpu/mod.py", rules=None,
           baseline=None, extra_files=()):
    """Analyze one fixture module (plus optional named extras) in a FRESH
    case dir (repeated calls in one test must not rescan prior fixtures);
    returns the AnalysisResult. The default filename puts the fixture inside
    a ``distar_tpu`` dir so package-scoped rules (no-print, metrics) apply."""
    run_on.case = getattr(run_on, "case", 0) + 1
    tmp_path = tmp_path / f"case{run_on.case}"
    path = tmp_path / filename
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    for name, text in extra_files:
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    analyzer = Analyzer(repo_root=str(tmp_path), rules=rules)
    return analyzer.run(collect_files([str(tmp_path)]), baseline=baseline)


def rules_of(result):
    return sorted(f.rule for f in result.findings)


# ===================================================================== locks
LOCK_HIT = """
    import threading, time

    class Pump:
        def __init__(self):
            self._lock = threading.Lock()

        def tick(self):
            with self._lock:
                time.sleep(0.1)
"""


def test_lock_held_blocking_hit(tmp_path):
    res = run_on(tmp_path, LOCK_HIT)
    assert "lock-held-blocking" in rules_of(res)


def test_lock_held_blocking_clean_outside_lock(tmp_path):
    res = run_on(tmp_path, """
        import threading, time

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()

            def tick(self):
                with self._lock:
                    n = 1
                time.sleep(0.1)
    """)
    assert "lock-held-blocking" not in rules_of(res)


def test_lock_condition_wait_on_held_lock_is_clean(tmp_path):
    """cond.wait() on the HELD condition releases it — the cv idiom."""
    res = run_on(tmp_path, """
        import threading

        class Q:
            def __init__(self):
                self._cv = threading.Condition()

            def pop(self):
                with self._cv:
                    self._cv.wait(timeout=1.0)
    """)
    assert "lock-held-blocking" not in rules_of(res)


def test_lock_event_wait_under_lock_is_flagged(tmp_path):
    res = run_on(tmp_path, """
        import threading

        class Q:
            def __init__(self):
                self._cv = threading.Condition()
                self._stop = threading.Event()

            def pop(self):
                with self._cv:
                    self._stop.wait(1.0)
    """)
    assert "lock-held-blocking" in rules_of(res)


def test_lock_callback_dispatch_hit_and_snapshot_clean(tmp_path):
    hit = run_on(tmp_path, """
        import threading

        class Emitter:
            def __init__(self):
                self._lock = threading.Lock()
                self._callbacks = []

            def emit(self, event):
                with self._lock:
                    for cb in self._callbacks:
                        cb(event)
    """)
    assert "lock-callback-dispatch" in rules_of(hit)
    clean = run_on(tmp_path, """
        import threading

        class Emitter:
            def __init__(self):
                self._lock = threading.Lock()
                self._callbacks = []

            def emit(self, event):
                with self._lock:
                    cbs = list(self._callbacks)
                for cb in cbs:
                    cb(event)
    """, filename="distar_tpu/mod2.py")
    assert "lock-callback-dispatch" not in rules_of(clean)


def test_lock_order_inversion(tmp_path):
    res = run_on(tmp_path, """
        import threading

        class S:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def one(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def two(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
    """)
    assert "lock-order-inversion" in rules_of(res)


def test_lock_nested_consistent_order_clean(tmp_path):
    res = run_on(tmp_path, """
        import threading

        class S:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def one(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def two(self):
                with self._a_lock:
                    with self._b_lock:
                        pass
    """)
    assert "lock-order-inversion" not in rules_of(res)


def test_closure_under_lock_not_flagged(tmp_path):
    """Code inside a def under a with-lock runs LATER, not under the lock."""
    res = run_on(tmp_path, """
        import threading, time

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def start(self):
                with self._lock:
                    def run():
                        time.sleep(1.0)
                    self._fn = run
    """)
    assert "lock-held-blocking" not in rules_of(res)


# ================================================================= lifecycle
def test_resource_unreleased_hit_and_clean(tmp_path):
    hit = run_on(tmp_path, """
        import socket

        class Server:
            def __init__(self):
                self._sock = socket.socket()
    """)
    assert "resource-unreleased" in rules_of(hit)
    clean = run_on(tmp_path, """
        import socket

        class Server:
            def __init__(self):
                self._sock = socket.socket()

            def stop(self):
                self._sock.close()
    """, filename="distar_tpu/mod2.py")
    assert "resource-unreleased" not in rules_of(clean)


def test_resource_tuple_swap_alias_counts_as_release(tmp_path):
    res = run_on(tmp_path, """
        import socket

        class Client:
            def __init__(self):
                self._sock = socket.socket()

            def close(self):
                sock, self._sock = self._sock, None
                if sock is not None:
                    sock.close()
    """)
    assert "resource-unreleased" not in rules_of(res)


def test_thread_unjoined_nondaemon_error_daemon_with_stop_warning(tmp_path):
    res = run_on(tmp_path, """
        import threading

        class A:
            def __init__(self):
                self._t = threading.Thread(target=self.run)

        class B:
            def __init__(self):
                self._t = threading.Thread(target=self.run, daemon=True)

            def stop(self):
                pass

        class C:
            def __init__(self):
                self._t = threading.Thread(target=self.run, daemon=True)
    """)
    found = {(f.ident, f.severity) for f in res.findings if f.rule == "thread-unjoined"}
    assert ("A._t unjoined", "error") in found
    assert ("B._t unjoined", "warning") in found
    assert not any(i.startswith("C._t") for i, _s in found)  # fire-and-forget daemon


# ====================================================================== wire
ERRORS_MOD = """
    class PlaneError(Exception):
        code = "plane_error"

        def to_wire(self):
            return {"code": self.code, "error": str(self)}

    class LostError(PlaneError):
        code = "lost"

    _WIRE_CODES = {cls.code: cls for cls in (PlaneError,)}

    def error_from_wire(payload):
        return _WIRE_CODES.get(payload.get("code"), PlaneError)(payload.get("error", ""))
"""


def test_wire_code_unregistered(tmp_path):
    res = run_on(tmp_path, ERRORS_MOD, filename="distar_tpu/plane/errors.py")
    hits = [f for f in res.findings if f.rule == "wire-code-unregistered"]
    assert len(hits) == 1 and "LostError" in hits[0].message


def test_wire_code_unknown_literal(tmp_path):
    res = run_on(
        tmp_path, """
        def dispatch(req):
            if not isinstance(req, dict):
                return {"code": "bad_stuff", "error": "nope"}
            return {"code": 0}
        """,
        filename="distar_tpu/plane/server.py",
        extra_files=[("distar_tpu/plane/errors.py", ERRORS_MOD)],
    )
    hits = [f for f in res.findings if f.rule == "wire-code-unknown"]
    assert len(hits) == 1 and "bad_stuff" in hits[0].message


def test_wire_code_registered_literal_clean(tmp_path):
    res = run_on(
        tmp_path, """
        def dispatch(req):
            if req.get("code") == "lost":
                return {"code": "plane_error", "error": "x"}
        """,
        filename="distar_tpu/plane/server.py",
        extra_files=[("distar_tpu/plane/errors.py", ERRORS_MOD)],
    )
    assert not [f for f in res.findings if f.rule == "wire-code-unknown"]


def test_handler_boundary_swallow(tmp_path):
    res = run_on(tmp_path, """
        class Handler:
            def do_POST(self):
                try:
                    self.route()
                except Exception:
                    pass
    """)
    assert "handler-boundary-swallow" in rules_of(res)


def test_handler_boundary_answering_is_clean(tmp_path):
    res = run_on(tmp_path, """
        class Handler:
            def do_POST(self):
                try:
                    payload = self.route()
                except Exception as e:
                    payload = {"code": 1, "info": repr(e)}
                self.send(payload)
    """)
    assert "handler-boundary-swallow" not in rules_of(res)


def test_retryable_swallowed_hit_and_counted_clean(tmp_path):
    hit = run_on(tmp_path, """
        from x import CommError

        def pull(client):
            try:
                client.fetch()
            except CommError:
                pass
    """)
    assert "retryable-swallowed" in rules_of(hit)
    clean = run_on(tmp_path, """
        from x import CommError

        def pull(client, errors):
            try:
                client.fetch()
            except CommError:
                errors.inc()
    """, filename="distar_tpu/mod2.py")
    assert "retryable-swallowed" not in rules_of(clean)


def test_retryable_swallowed_teardown_exempt(tmp_path):
    res = run_on(tmp_path, """
        from x import CommError

        class C:
            def close(self):
                try:
                    self._sock.close()
                except CommError:
                    pass
    """)
    assert "retryable-swallowed" not in rules_of(res)


# ======================================================================= jax
def test_jax_donated_host_leaf(tmp_path):
    res = run_on(tmp_path, """
        import jax
        import numpy as np

        step = jax.jit(lambda s: s, donate_argnums=(0,))

        def train(batch):
            state = np.zeros((4,))
            return step(state)
    """)
    assert "jax-donated-host-leaf" in rules_of(res)


def test_jax_donated_placed_leaf_clean(tmp_path):
    res = run_on(tmp_path, """
        import jax
        import numpy as np

        step = jax.jit(lambda s: s, donate_argnums=(0,))

        def train(batch, sharding):
            state = np.zeros((4,))
            state = jax.device_put(state, sharding)
            return step(state)
    """)
    assert "jax-donated-host-leaf" not in rules_of(res)


def test_jax_device_get_in_loop(tmp_path):
    hit = run_on(tmp_path, """
        import jax

        def decollate(leaves):
            out = []
            for leaf in leaves:
                out.append(jax.device_get(leaf))
            return out
    """)
    assert "jax-device-get-in-loop" in rules_of(hit)
    clean = run_on(tmp_path, """
        import jax

        def decollate(tree):
            host = jax.device_get(tree)
            return [host[k] for k in host]
    """, filename="distar_tpu/mod2.py")
    assert "jax-device-get-in-loop" not in rules_of(clean)


def test_jax_nondeterministic_jit(tmp_path):
    res = run_on(tmp_path, """
        import jax, time

        @jax.jit
        def step(x):
            t = time.time()
            return x + t
    """)
    assert "jax-nondeterministic-jit" in rules_of(res)


def test_jax_nondeterministic_pure_callback_target(tmp_path):
    res = run_on(tmp_path, """
        import jax, time

        def host_fn(x):
            return x * time.time()

        def model(x):
            return jax.pure_callback(host_fn, x, x)
    """)
    assert "jax-nondeterministic-jit" in rules_of(res)


# =================================================================== hygiene
def test_no_print_library_vs_bin(tmp_path):
    res = run_on(tmp_path, "print('hi')\n")
    assert "no-print" in rules_of(res)
    res2 = run_on(tmp_path, "print('hi')\n", filename="distar_tpu/bin/cli.py")
    assert "no-print" not in rules_of(res2)


def test_socket_rules(tmp_path):
    res = run_on(tmp_path, """
        import socket, urllib.request

        def f():
            try:
                urllib.request.urlopen("http://x")
            except:
                pass
            socket.create_connection(("h", 1))
            socket.create_connection(("h", 1), timeout=3)
    """)
    rs = rules_of(res)
    assert rs.count("socket-no-timeout") == 2
    assert "socket-bare-except" in rs


def test_metric_kind_misuse_set_on_counter(tmp_path):
    res = run_on(tmp_path, """
        from .obs import get_registry

        def f(reg):
            reg.counter("distar_x_total", "help").set(3)
    """)
    assert "metric-kind-misuse" in rules_of(res)


def test_metric_kind_misuse_total_gauge(tmp_path):
    res = run_on(tmp_path, """
        def f(reg):
            g = reg.gauge("distar_x_total", "help")
            g.set(1)
    """)
    assert "metric-kind-misuse" in rules_of(res)


def test_metric_inc_only_gauge_flagged_inc_dec_clean(tmp_path):
    hit = run_on(tmp_path, """
        def f(reg):
            g = reg.gauge("distar_x_things", "help")
            g.inc()
    """)
    assert any(f.rule == "metric-kind-misuse" and "inc()ed" in f.message
               for f in hit.findings)
    clean = run_on(tmp_path, """
        def f(reg):
            g = reg.gauge("distar_x_things", "help")
            g.inc()
            g.dec()
    """, filename="distar_tpu/mod2.py")
    assert not any(f.rule == "metric-kind-misuse" for f in clean.findings)


def test_metric_label_cardinality(tmp_path):
    res = run_on(tmp_path, """
        def f(reg, payload):
            reg.counter("distar_x_total", "help", session=payload["session_id"]).inc()
    """)
    assert "metric-label-cardinality" in rules_of(res)


# ================================================================== pragmas
def test_pragma_suppresses_with_reason(tmp_path):
    res = run_on(tmp_path, """
        import threading, time

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()

            def tick(self):
                with self._lock:
                    # analysis: allow(lock-held-blocking) — simulated chip contention is the point here
                    time.sleep(0.1)
    """)
    assert "lock-held-blocking" not in rules_of(res)
    assert any(f.rule == "lock-held-blocking" for f, _why in res.suppressed)


def test_pragma_without_reason_is_itself_a_finding(tmp_path):
    res = run_on(tmp_path, """
        import threading, time

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()

            def tick(self):
                with self._lock:
                    time.sleep(0.1)  # analysis: allow(lock-held-blocking)
    """)
    assert "pragma-no-reason" in rules_of(res)


def test_legacy_marker_still_suppresses(tmp_path):
    res = run_on(tmp_path, "print('x')  # lint: allow-print\n")
    assert "no-print" not in rules_of(res)


# ================================================================== baseline
def test_baseline_round_trip_and_shrink_only(tmp_path):
    src = LOCK_HIT
    res = run_on(tmp_path, src)
    assert res.findings and res.exit_code == 2

    # write the baseline from the findings: same tree is now baselined-only
    bl_path = tmp_path / "baseline.json"
    save_baseline(str(bl_path), res.findings)
    entries = load_baseline(str(bl_path))
    res2 = run_on(tmp_path, src, baseline=entries)
    assert res2.exit_code == 1
    assert not res2.findings and len(res2.baselined) == len(entries)

    # shrink-only: fix the code but keep the baseline entry -> stale = error
    res3 = run_on(tmp_path, """
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
    """, baseline=entries)
    assert res3.stale_baseline and res3.exit_code == 2


def test_apply_baseline_multiset_semantics():
    from distar_tpu.analysis import Finding

    f = Finding(rule="r", severity="error", path="p.py", line=3, message="m")
    g = Finding(rule="r", severity="error", path="p.py", line=9, message="m")
    entries = [{"rule": "r", "path": "p.py", "ident": "m"}]
    new, matched, stale = apply_baseline([f, g], entries)
    assert len(matched) == 1 and len(new) == 1 and not stale


def test_render_markdown_shapes(tmp_path):
    res = run_on(tmp_path, LOCK_HIT)
    md = render_markdown(res)
    assert "lock-held-blocking" in md and "verdict" in md


# =================================================================== driver
def test_analyze_cli_report_and_exit_codes(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "analyze.py"), "report",
         "distar_tpu/analysis"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode in (0, 1), out.stdout + out.stderr
    assert "verdict" in out.stdout


def test_analyze_cli_changed_mode_runs(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "analyze.py"), "--changed"],
        capture_output=True, text=True, cwd=REPO,
    )
    # whatever git reports changed right now must be analyzable and clean
    # against the committed baseline (or there is nothing changed at all)
    assert out.returncode in (0, 1), out.stdout + out.stderr


# ============================================================ legacy shims
def test_legacy_shim_surfaces(tmp_path):
    """The three legacy lint CLIs keep their import surface and semantics.
    Whole-tree cleanliness is already covered by the pre-existing lint
    tests (test_obs_metrics/test_resilience) + test_analysis_repo_clean, so
    this exercises the shims on a small fixture instead of re-scanning the
    package three times."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import lint_metric_names as lmn
        import lint_no_print as lnp
        import lint_sockets as ls
    finally:
        sys.path.pop(0)
    pkg = tmp_path / "distar_tpu"
    (pkg / "utils").mkdir(parents=True)
    (pkg / "bin").mkdir()
    (pkg / "mod.py").write_text(
        "import socket\n"
        "print('offends')\n"
        "print('allowed')  # lint: allow-print\n"
        "socket.create_connection(('h', 1))\n"
        "try:\n    pass\nexcept:\n    pass\n"
        "def f(reg):\n    reg.counter('wrong_name', 'h').inc()\n"
    )
    (pkg / "bin" / "cli.py").write_text("print('cli stdout is fine')\n")
    prints = lnp.find_bare_prints(str(pkg))
    assert [(p, l) for (p, l, _t) in prints] == [("mod.py", 2)]
    offences = ls.find_offences(str(pkg))
    msgs = [m for (_p, _l, m) in offences]
    assert len(offences) == 2
    assert any("create_connection" in m for m in msgs)
    assert any("bare 'except:'" in m for m in msgs)
    docs = tmp_path / "obs.md"
    docs.write_text("`distar_ok_total` is documented\n")
    problems = lmn.lint(str(pkg), str(docs))
    assert len(problems) == 1 and "wrong_name" in problems[0]
    names = lmn.registered_names(str(pkg))
    assert "wrong_name" in names
    assert "distar_stopwatch_seconds" not in names  # went with utils/timing.StopWatch (PR 23)


# ================================================================= lockwatch
LOCKWATCH_ABBA = """
import sys, threading, time
sys.path.insert(0, %(repo)r)
from distar_tpu.analysis import lockwatch

lockwatch.install(filters=("abba_fixture",))
A = threading.Lock()
B = threading.Lock()
hold_a = threading.Event()
hold_b = threading.Event()

def one():
    with A:
        hold_a.set()
        hold_b.wait(2.0)
        acquired = B.acquire(timeout=0.2)   # real contention, times out
        if acquired:
            B.release()

def two():
    with B:
        hold_b.set()
        hold_a.wait(2.0)
        acquired = A.acquire(timeout=0.2)
        if acquired:
            A.release()

t1 = threading.Thread(target=one)
t2 = threading.Thread(target=two)
t1.start(); t2.start(); t1.join(); t2.join()
rep = lockwatch.report()
import json
print("LOCKWATCH-JSON " + json.dumps(rep))
"""


def test_lockwatch_reports_real_abba_cycle(tmp_path):
    """Two real threads acquire (A then B) and (B then A) concurrently —
    lockwatch must report the inversion and the cycle even though the run
    itself survived (acquire timeouts)."""
    script = tmp_path / "abba_fixture.py"
    script.write_text(LOCKWATCH_ABBA % {"repo": REPO})
    out = subprocess.run([sys.executable, str(script)],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    line = next(l for l in out.stdout.splitlines() if l.startswith("LOCKWATCH-JSON "))
    rep = json.loads(line[len("LOCKWATCH-JSON "):])
    assert len(rep["inversions"]) == 1, rep["inversions"]
    assert rep["cycles"], "DFS must find the A->B->A cycle"
    inv = rep["inversions"][0]
    assert "abba_fixture.py" in inv["a"] and "abba_fixture.py" in inv["b"]


def test_lockwatch_held_blocking_and_condition_exemption():
    """In-process: a sleep under a watched lock is reported; cond.wait on
    the held condition is NOT (the proxy's _release_save shows it released).
    Installed/uninstalled around the assertions so the suite is unaffected."""
    from distar_tpu.analysis import lockwatch

    if lockwatch.installed():  # DISTAR_LOCKWATCH=1 session: don't fight it
        pytest.skip("lockwatch already active for this session")
    lockwatch.install(filters=("test_analysis",))
    try:
        lock = threading.Lock()
        with lock:
            time.sleep(0.01)
        cv = threading.Condition()
        hit = []

        def waiter():
            with cv:
                cv.wait(timeout=0.3)
                hit.append(1)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cv:
            cv.notify()
        t.join()
        rep = lockwatch.report()
    finally:
        lockwatch.uninstall()
        lockwatch.reset()
    assert hit == [1]
    blockers = {(h["blocker"]) for h in rep["held_blocking"]}
    assert "time.sleep" in blockers
    # the condition's own wait never shows as held-while-blocking
    assert not any("Condition" in b for b in blockers)


def test_lockwatch_baseline_matching():
    from distar_tpu.analysis import lockwatch

    rep = {
        "held_blocking": [
            {"lock": "distar_tpu/a.py:10", "blocker": "socket.recv",
             "caller": "distar_tpu/b.py:5", "count": 3},
        ],
        "inversions": [
            {"a": "distar_tpu/a.py:10", "b": "distar_tpu/c.py:7",
             "count_ab": 1, "count_ba": 1},
        ],
    }
    baseline = {
        "held_blocking": [
            {"lock_file": "distar_tpu/a.py", "blocker": "socket.recv",
             "why": "request lock IS the serializer"},
        ],
        "inversions": [],
    }
    bad = lockwatch.unbaselined(rep, baseline)
    assert bad["held_blocking"] == []          # justified
    assert len(bad["inversions"]) == 1         # not justified
    assert not bad["stale"]
    # an entry without a why never matches
    baseline["held_blocking"][0]["why"] = ""
    bad2 = lockwatch.unbaselined(rep, baseline)
    assert len(bad2["held_blocking"]) == 1


# ===================================== regressions for analyzer-found bugs
# Each test pins one genuine bug this PR's analyzer surfaced and fixed
# (docs/analysis.md "incidents" section names them).


def test_wire_bad_request_rehydrates_typed_both_planes():
    """bad_frame/bad_request/shm_error used to cross the wire as raw string
    literals no registry knew — peers degraded them to the base class."""
    from distar_tpu.replay import errors as replay_errors
    from distar_tpu.serve import errors as serve_errors

    e = serve_errors.error_from_wire({"code": "bad_request", "error": "unknown op"})
    assert isinstance(e, serve_errors.BadRequestError)
    e = serve_errors.error_from_wire({"code": "bad_frame", "error": "garbage"})
    assert isinstance(e, serve_errors.BadFrameError)
    e = replay_errors.error_from_wire({"code": "bad_request", "error": "x"})
    assert isinstance(e, replay_errors.BadRequestError)

    # the shm ring pump's dispatch-bug reply is registered on BOTH planes
    from distar_tpu.comm.shm_ring import ShmError

    wire = ShmError("boom", op="pump").to_wire()
    assert wire["code"] == "shm_error"
    assert isinstance(replay_errors.error_from_wire(wire),
                      replay_errors.RingServiceError)
    assert isinstance(serve_errors.error_from_wire(wire),
                      serve_errors.RingServiceError)


def test_serve_tcp_unknown_op_answers_typed():
    from distar_tpu.serve.errors import BadRequestError
    from distar_tpu.serve.tcp_frontend import ServeTCPServer

    class _Gw:
        pass

    srv = ServeTCPServer(_Gw(), port=0)
    wire = srv._dispatch({"op": "definitely_not_an_op"})
    assert wire["code"] == BadRequestError.code
    wire2 = srv._dispatch(["not", "a", "dict"])
    assert wire2["code"] == BadRequestError.code


def test_coordinator_server_stop_joins_serve_thread():
    """stop() used to return while the serve_forever thread could still be
    running (server_close racing the loop)."""
    from distar_tpu.comm.coordinator import CoordinatorServer

    srv = CoordinatorServer()
    srv.start()
    thread = srv._thread
    srv.stop()
    assert srv._thread is None
    assert thread is not None and not thread.is_alive()


def test_replay_admin_stop_joins_and_drain_hook_failure_counted(tmp_path):
    import urllib.request

    from distar_tpu.obs.registry import MetricsRegistry, set_registry
    from distar_tpu.replay.server import ReplayAdminServer
    from distar_tpu.replay.store import ReplayStore, TableConfig

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        store = ReplayStore(table_factory=lambda n: TableConfig())

        def bad_hook():
            raise RuntimeError("deregister exploded")

        admin = ReplayAdminServer(store, port=0, on_drain=bad_hook).start()
        thread = admin._thread
        try:
            req = urllib.request.Request(
                f"http://{admin.host}:{admin.port}/drain", data=b"{}", method="POST")
            body = urllib.request.urlopen(req, timeout=5).read()
            assert b'"code": 0' in body  # drain proceeds; hook is best-effort
            # ... but never silently: the failure is counted now
            assert reg.counter("distar_replay_drain_hook_errors_total").value == 1
        finally:
            admin.stop()
        assert not thread.is_alive()
    finally:
        set_registry(prev)


def test_scalar_sink_close_releases_file(tmp_path):
    from distar_tpu.utils.log import ScalarSink

    sink = ScalarSink(str(tmp_path / "scalars"), force_jsonl=True)
    sink.add_scalar("a", 1.0, 0)
    f = sink._file
    sink.close()
    assert f.closed
    sink.close()  # idempotent


def test_shm_peer_close_joins_beat_thread():
    pytest.importorskip("multiprocessing.shared_memory")
    from distar_tpu.comm import shm_ring

    try:
        peer, _fields = shm_ring.mint_ring_pair(ring_bytes=1 << 16)
    except shm_ring.ShmUnavailableError:
        pytest.skip("no shared memory on this host")
    beat = peer._beat_thread
    peer.close()
    assert not beat.is_alive(), "close() must reap the beat thread before unlink"


# ================================================================ tier-1 gate
def test_analysis_repo_clean():
    """THE gate: the full analyzer over the committed tree must be clean
    (exit 0) or baselined-only (exit 1) against the committed baseline —
    any new finding fails tier-1, mirroring the legacy lint-from-tests
    idiom. Stale baseline entries fail too (shrink-only)."""
    baseline = load_baseline(os.path.join(REPO, "tools", "analysis_baseline.json"))
    analyzer = Analyzer(repo_root=REPO)
    files = collect_files(["distar_tpu", "tools"], repo_root=REPO)
    result = analyzer.run(files, baseline=baseline)
    msg = "\n".join(str(f) for f in result.findings) or "<none>"
    stale = "\n".join(str(e) for e in result.stale_baseline) or "<none>"
    assert result.exit_code in (0, 1), (
        f"new analyzer findings:\n{msg}\nstale baseline entries:\n{stale}\n"
        f"fix the code, add a `# analysis: allow(<rule>) — <why>` pragma, "
        f"or (last resort) baseline via tools/analyze.py --write-baseline"
    )
    # the committed baseline must stay small: grandfathered debt only
    assert len(baseline) <= 25, "baseline may only shrink (ISSUE 14 contract)"
