"""cituslint framework tests: every rule group fires on a bad fixture,
stays quiet on the equivalent good one, and the suppression pragma
behaves (honored when justified, itself a diagnostic when not)."""

import textwrap

import pytest

from tools.cituslint import run_lint


def make_pkg(tmp_path, files: dict) -> str:
    """Write a synthetic package and return its path."""
    pkg = tmp_path / "fixturepkg"
    pkg.mkdir(parents=True, exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(pkg)


def ids(diags):
    return [d.rule_id for d in diags]


# ------------------------------------------------------------- LOCK01

LOCKY_BAD = """
    import threading

    class Box:
        def __init__(self):
            self._mu = threading.Lock()
            self.items = []

        def add(self, x):
            with self._mu:
                self.items.append(x)

        def drop(self):
            self.items = []
"""

LOCKY_GOOD = """
    import threading

    class Box:
        def __init__(self):
            self._mu = threading.Lock()
            self.items = []

        def add(self, x):
            with self._mu:
                self.items.append(x)

        def drop(self):
            with self._mu:
                self.items = []
"""


def test_lock_rule_fires_on_unguarded_write(tmp_path):
    diags = run_lint(make_pkg(tmp_path, {"box.py": LOCKY_BAD}),
                     select={"LOCK01"})
    assert ids(diags) == ["LOCK01"]
    assert "drop" in diags[0].message and "items" in diags[0].message


def test_lock_rule_quiet_when_guarded(tmp_path):
    assert run_lint(make_pkg(tmp_path, {"box.py": LOCKY_GOOD}),
                    select={"LOCK01"}) == []


def test_lock_rule_locked_suffix_convention(tmp_path):
    src = """
        import threading

        class Box:
            def __init__(self):
                self._mu = threading.Lock()
                self.items = []

            def add(self, x):
                with self._mu:
                    self._add_locked(x)

            def _add_locked(self, x):
                self.items.append(x)

            def sneak(self, x):
                self._add_locked(x)
    """
    diags = run_lint(make_pkg(tmp_path, {"box.py": src}),
                     select={"LOCK01"})
    # _add_locked's own mutation is fine (caller holds the lock); the
    # unguarded CALL from sneak() is the finding
    assert len(diags) == 1
    assert "sneak" in diags[0].message and "_add_locked" in diags[0].message


def test_lock_rule_ignores_init(tmp_path):
    src = """
        import threading

        class Box:
            def __init__(self):
                self._mu = threading.Lock()
                self.items = []
                self.items = ["seed"]

            def add(self, x):
                with self._mu:
                    self.items.append(x)
    """
    assert run_lint(make_pkg(tmp_path, {"box.py": src}),
                    select={"LOCK01"}) == []


# ------------------------------------------------------------- CONF01

def test_confinement_fires_outside_blessed_module(tmp_path):
    pkg = make_pkg(tmp_path, {
        "utils/__init__.py": "",
        "utils/clock.py": "import time\n\ndef now():\n    return time.time()\n",
        "stray.py": "import time\n\ndef f():\n    return time.time()\n",
    })
    diags = run_lint(pkg, select={"CONF01"})
    assert len(diags) == 1
    assert diags[0].path.endswith("stray.py")
    assert "time.time" in diags[0].message


def test_confinement_resolves_import_aliases(tmp_path):
    pkg = make_pkg(tmp_path, {
        "stray.py": "import time as _t\n\ndef f():\n    return _t.time()\n",
    })
    diags = run_lint(pkg, select={"CONF01"})
    assert len(diags) == 1 and "time.time" in diags[0].message


def test_confinement_quiet_in_blessed_module(tmp_path):
    pkg = make_pkg(tmp_path, {
        "utils/__init__.py": "",
        "utils/clock.py": "import time\n\ndef now():\n    return time.time()\n",
    })
    assert run_lint(pkg, select={"CONF01"}) == []


def test_thread_rules(tmp_path):
    src = """
        import threading

        def bad():
            t = threading.Thread(target=print)
            t.start()

        def good():
            t = threading.Thread(target=print, daemon=False)
            t.start()
            t.join()
    """
    diags = run_lint(make_pkg(tmp_path, {"threads.py": src}),
                     select={"THR01", "THR02"})
    # bad(): missing daemon= (THR01).  THR02 is module-scoped on the
    # bound name: 't' IS joined (in good), so only THR01 fires here.
    assert ids(diags) == ["THR01"]

    src2 = """
        import threading

        def fire_and_forget():
            threading.Thread(target=print, daemon=True).start()
    """
    diags2 = run_lint(make_pkg(tmp_path / "p2", {"threads2.py": src2}),
                      select={"THR01", "THR02"})
    assert ids(diags2) == ["THR02"]


# ------------------------------------------------------------- SWL01

def test_silent_swallow_fires(tmp_path):
    src = """
        def f():
            try:
                risky()
            except Exception:
                pass
    """
    diags = run_lint(make_pkg(tmp_path, {"m.py": src}), select={"SWL01"})
    assert ids(diags) == ["SWL01"]


def test_bare_except_fires(tmp_path):
    src = """
        def f():
            for _ in range(3):
                try:
                    risky()
                except:
                    continue
    """
    diags = run_lint(make_pkg(tmp_path, {"m.py": src}), select={"SWL01"})
    assert ids(diags) == ["SWL01"]
    assert "bare except" in diags[0].message


def test_swallow_with_handling_is_quiet(tmp_path):
    src = """
        def f(counters):
            try:
                risky()
            except Exception:
                counters.bump("errors")
            try:
                risky()
            except ValueError:
                pass  # narrow catch: not SWL01's business
    """
    assert run_lint(make_pkg(tmp_path, {"m.py": src}),
                    select={"SWL01"}) == []


# ----------------------------------------------------------- CNT01/02

STATS_FIXTURE = """
    class StatCounters:
        COUNTERS = [
            "queries_executed",
            "errors_seen",
        ]
"""


def test_undeclared_counter_bump_fires(tmp_path):
    pkg = make_pkg(tmp_path, {
        "stats.py": STATS_FIXTURE,
        "m.py": ("def f(c):\n    c.bump('queries_executed')\n"
                 "    c.bump('made_up_name')\n"),
    })
    diags = run_lint(pkg, select={"CNT01"})
    assert len(diags) == 1 and "made_up_name" in diags[0].message


def test_dead_counter_fires(tmp_path):
    pkg = make_pkg(tmp_path, {
        "stats.py": STATS_FIXTURE,
        "m.py": "def f(c):\n    c.bump('queries_executed')\n",
    })
    diags = run_lint(pkg, select={"CNT02"})
    assert len(diags) == 1 and "errors_seen" in diags[0].message


def test_declared_and_used_counters_quiet(tmp_path):
    pkg = make_pkg(tmp_path, {
        "stats.py": STATS_FIXTURE,
        "m.py": ("def f(c):\n    c.bump('queries_executed')\n"
                 "    c.bump_max('errors_seen', 2)\n"),
    })
    assert run_lint(pkg, select={"CNT01", "CNT02"}) == []


def test_undeclared_record_tally_fires(tmp_path):
    """``record.tally("name", n)`` (executor/pipeline.py PipelineStats)
    books a statement's figure and bumps the counter of that name: an
    undeclared name fires as a bump of it would."""
    pkg = make_pkg(tmp_path, {
        "stats.py": STATS_FIXTURE,
        "m.py": ("def f(record):\n"
                 "    record.tally('queries_executed', 1)\n"
                 "    record.tally('made_up_figure', 3, add=True)\n"),
    })
    diags = run_lint(pkg, select={"CNT01"})
    assert len(diags) == 1 and "made_up_figure" in diags[0].message


def test_counter_booked_through_a_record_alone_is_not_dead(tmp_path):
    pkg = make_pkg(tmp_path, {
        "stats.py": STATS_FIXTURE,
        "m.py": ("def f(record):\n"
                 "    record.tally('queries_executed', 1)\n"
                 "    record.tally('errors_seen', 2, add=True)\n"),
    })
    assert run_lint(pkg, select={"CNT01", "CNT02"}) == []


# --------------------------------------------------------------- CNT03

WAITS_FIXTURE = """
    class StatCounters:
        COUNTERS = ["wait_lock_ms", "wait_remote_rpc_ms"]

    WAIT_COUNTERS = {
        "lock": "wait_lock_ms",
        "remote_rpc": "wait_remote_rpc_ms",
    }
"""


def test_unregistered_wait_event_fires(tmp_path):
    pkg = make_pkg(tmp_path, {
        "stats.py": WAITS_FIXTURE,
        "m.py": ("from stats import begin_wait\n"
                 "def f():\n"
                 "    begin_wait('lock')\n"
                 "    begin_wait('remote_rpc')\n"
                 "    begin_wait('made_up_stall')\n"),
    })
    diags = run_lint(pkg, select={"CNT03"})
    assert len(diags) == 1 and "made_up_stall" in diags[0].message


def test_unentered_wait_event_fires(tmp_path):
    pkg = make_pkg(tmp_path, {
        "stats.py": WAITS_FIXTURE,
        "m.py": ("def f(stats):\n"
                 "    stats.begin_wait('lock')\n"),
    })
    diags = run_lint(pkg, select={"CNT03"})
    assert len(diags) == 1 and "remote_rpc" in diags[0].message


def test_registered_and_entered_wait_events_quiet(tmp_path):
    pkg = make_pkg(tmp_path, {
        "stats.py": WAITS_FIXTURE,
        "m.py": ("from stats import begin_wait\n"
                 "def f(stats):\n"
                 "    begin_wait('lock')\n"
                 "    stats.begin_wait('remote_rpc')\n"),
    })
    assert run_lint(pkg, select={"CNT03"}) == []


# --------------------------------------------------------------- CNT04

RECORDER_FIXTURE = """
    HEALTH_EVENT_KINDS = {
        "p99_regression": "p99 above baseline",
        "dead_node": "endpoint unreachable",
    }
"""

# both kinds surfaced: export uses the health_<kind> gauge spelling,
# utility uses the bare kind in its severity row table
EXPORT_FIXTURE = ('def g(d, active):\n'
                  '    d["health_p99_regression"] = active\n'
                  '    d["health_dead_node"] = active\n')
UTILITY_FIXTURE = ('SEV = {"p99_regression": "warning",\n'
                   '       "dead_node": "critical"}\n')

CNT04_BASE = {
    "observability/__init__.py": "",
    "commands/__init__.py": "",
    "observability/flight_recorder.py": RECORDER_FIXTURE,
}


def test_health_kind_missing_gauge_fires(tmp_path):
    pkg = make_pkg(tmp_path, {
        **CNT04_BASE,
        "observability/export.py":
            'def g(d, active):\n    d["health_p99_regression"] = active\n',
        "commands/utility.py": UTILITY_FIXTURE,
    })
    diags = run_lint(pkg, select={"CNT04"})
    assert len(diags) == 1
    assert "dead_node" in diags[0].message
    assert "Prometheus" in diags[0].message


def test_health_kind_missing_row_type_fires(tmp_path):
    pkg = make_pkg(tmp_path, {
        **CNT04_BASE,
        "observability/export.py": EXPORT_FIXTURE,
        "commands/utility.py": 'SEV = {"p99_regression": "warning"}\n',
    })
    diags = run_lint(pkg, select={"CNT04"})
    assert len(diags) == 1
    assert "dead_node" in diags[0].message
    assert "citus_health_events" in diags[0].message


def test_undeclared_emit_kind_fires(tmp_path):
    pkg = make_pkg(tmp_path, {
        **CNT04_BASE,
        "observability/export.py": EXPORT_FIXTURE,
        "commands/utility.py": UTILITY_FIXTURE,
        "m.py": ("def f(rec):\n"
                 "    rec.emit_event('p99_regression', 'x', 1, 0, 'd')\n"
                 "    rec.emit_event('made_up_alarm', 'x', 1, 0, 'd')\n"),
    })
    diags = run_lint(pkg, select={"CNT04"})
    assert len(diags) == 1 and "made_up_alarm" in diags[0].message


def test_health_kinds_fully_surfaced_quiet(tmp_path):
    pkg = make_pkg(tmp_path, {
        **CNT04_BASE,
        "observability/export.py": EXPORT_FIXTURE,
        "commands/utility.py": UTILITY_FIXTURE,
        "m.py": ("def f(rec):\n"
                 "    rec.emit_event('dead_node', 'h:1', 1, 0, 'down')\n"),
    })
    assert run_lint(pkg, select={"CNT04"}) == []


# ------------------------------------------------------------- GUC01

CONFIG_FIXTURE = """
    from dataclasses import dataclass, field

    @dataclass
    class PlannerSettings:
        shard_cap: int = 8

    @dataclass
    class Settings:
        planner: PlannerSettings = field(default_factory=PlannerSettings)
        verbose: bool = False

        def replace(self, **kw):
            return self
"""

GUCS_FIXTURE = """
    _GUCS = {
        "citus.shard_cap": ("planner", "shard_cap", int),
        "citus.verbose": (None, "verbose", "bool"),
    }
"""


def test_settings_typo_fires(tmp_path):
    pkg = make_pkg(tmp_path, {
        "config.py": CONFIG_FIXTURE,
        "commands/__init__.py": "",
        "commands/config_cmds.py": GUCS_FIXTURE,
        "m.py": "def f(settings):\n    return settings.planner.shardcap\n",
    })
    diags = run_lint(pkg, select={"GUC01"})
    assert len(diags) == 1 and "shardcap" in diags[0].message


def test_settings_uncovered_field_fires(tmp_path):
    pkg = make_pkg(tmp_path, {
        "config.py": CONFIG_FIXTURE,
        "commands/__init__.py": "",
        "commands/config_cmds.py": "_GUCS = {}\n",
        "m.py": "def f(settings):\n    return settings.planner.shard_cap\n",
    })
    diags = run_lint(pkg, select={"GUC01"})
    assert len(diags) == 1 and "SET/SHOW" in diags[0].message


def test_settings_covered_reads_quiet(tmp_path):
    pkg = make_pkg(tmp_path, {
        "config.py": CONFIG_FIXTURE,
        "commands/__init__.py": "",
        "commands/config_cmds.py": GUCS_FIXTURE,
        "m.py": ("def f(settings):\n"
                 "    return settings.planner.shard_cap, settings.verbose\n"),
    })
    assert run_lint(pkg, select={"GUC01"}) == []


# -------------------------------------------------------- suppressions

def test_justified_suppression_honored(tmp_path):
    src = """
        def f():
            try:
                risky()
            # lint: disable=SWL01 -- probe only; failure falls back
            except Exception:
                pass
    """
    assert run_lint(make_pkg(tmp_path, {"m.py": src})) == []


def test_trailing_suppression_honored(tmp_path):
    src = """
        import time

        def f():
            return time.time()  # lint: disable=CONF01 -- wall-clock display only
    """
    assert run_lint(make_pkg(tmp_path, {"m.py": src}),
                    select={"CONF01"}) == []


def test_unjustified_suppression_rejected(tmp_path):
    src = """
        def f():
            try:
                risky()
            # lint: disable=SWL01
            except Exception:
                pass
    """
    diags = run_lint(make_pkg(tmp_path, {"m.py": src}))
    got = set(ids(diags))
    # the swallow STILL fires (no justification => no suppression) and
    # the bare pragma is its own finding
    assert got == {"SWL01", "SUP01"}


def test_unknown_rule_id_in_pragma_rejected(tmp_path):
    src = """
        X = 1  # lint: disable=NOPE99 -- misremembered id
    """
    diags = run_lint(make_pkg(tmp_path, {"m.py": src}))
    assert ids(diags) == ["SUP02"]
    assert "NOPE99" in diags[0].message


def test_suppression_only_covers_named_rule(tmp_path):
    src = """
        import time

        def f():
            try:
                return time.time()
            # lint: disable=CONF01 -- wrong id for the swallow below
            except Exception:
                pass
    """
    diags = run_lint(make_pkg(tmp_path, {"m.py": src}),
                     select={"SWL01", "CONF01"})
    assert "SWL01" in ids(diags)


# ------------------------------------------------------------ engine

def test_parse_error_is_a_diagnostic(tmp_path):
    diags = run_lint(make_pkg(tmp_path, {"broken.py": "def f(:\n"}))
    assert ids(diags) == ["PARSE"]


def test_missing_package_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        run_lint(str(tmp_path / "no_such_pkg"))


def test_diagnostics_sorted_and_unique(tmp_path):
    src = """
        def f():
            try:
                risky()
            except Exception:
                pass

        def g():
            try:
                risky()
            except Exception:
                pass
    """
    diags = run_lint(make_pkg(tmp_path, {"b.py": src, "a.py": src}),
                     select={"SWL01"})
    assert len(diags) == 4
    assert diags == sorted(diags)


def test_cli_main_exit_codes(tmp_path, capsys):
    from tools.cituslint.__main__ import main
    pkg = make_pkg(tmp_path, {"m.py": "def f():\n    try:\n        x()\n"
                                      "    except Exception:\n        pass\n"})
    assert main([pkg]) == 1
    out = capsys.readouterr().out
    assert "SWL01" in out
    clean = make_pkg(tmp_path / "c", {"m.py": "X = 1\n"})
    assert main([clean]) == 0
    assert main(["--list-rules"]) == 0


# ------------------------------------------------------------- LOCK02

ORDER_CYCLE_2 = """
    import threading

    class Pair:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def ab(self):
            with self._a:
                with self._b:
                    pass

        def ba(self):
            with self._b:
                with self._a:
                    pass
"""

ORDER_CONSISTENT = """
    import threading

    class Pair:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._a:
                with self._b:
                    pass
"""


def test_lock_order_two_lock_cycle(tmp_path):
    diags = run_lint(make_pkg(tmp_path, {"pair.py": ORDER_CYCLE_2}),
                     select={"LOCK02"})
    assert ids(diags) == ["LOCK02"]
    msg = diags[0].message
    assert "cycle" in msg and "Pair._a" in msg and "Pair._b" in msg


def test_lock_order_consistent_is_quiet(tmp_path):
    assert run_lint(make_pkg(tmp_path, {"pair.py": ORDER_CONSISTENT}),
                    select={"LOCK02"}) == []


def test_lock_order_three_lock_rotation(tmp_path):
    src = """
        import threading

        class Trio:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self._c = threading.Lock()

            def ab(self):
                with self._a:
                    with self._b:
                        pass

            def bc(self):
                with self._b:
                    with self._c:
                        pass

            def ca(self):
                with self._c:
                    with self._a:
                        pass
    """
    diags = run_lint(make_pkg(tmp_path, {"trio.py": src}),
                     select={"LOCK02"})
    assert ids(diags) == ["LOCK02"]
    for node in ("Trio._a", "Trio._b", "Trio._c"):
        assert node in diags[0].message


def test_lock_order_resolves_through_locked_helper(tmp_path):
    src = """
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def _grab_b_locked(self):
                with self._b:
                    pass

            def ab(self):
                with self._a:
                    self._grab_b_locked()

            def ba(self):
                with self._b:
                    with self._a:
                        pass
    """
    diags = run_lint(make_pkg(tmp_path, {"pair.py": src}),
                     select={"LOCK02"})
    assert ids(diags) == ["LOCK02"]
    assert "cycle" in diags[0].message


def test_lock_order_self_reacquire_nonreentrant(tmp_path):
    src = """
        import threading

        class Box:
            def __init__(self):
                self._mu = threading.Lock()

            def outer(self):
                with self._mu:
                    self._inner()

            def _inner(self):
                with self._mu:
                    pass
    """
    diags = run_lint(make_pkg(tmp_path, {"box.py": src}),
                     select={"LOCK02"})
    assert ids(diags) == ["LOCK02"]
    assert "self-deadlock" in diags[0].message


def test_lock_order_rlock_reacquire_is_quiet(tmp_path):
    src = """
        import threading

        class Box:
            def __init__(self):
                self._mu = threading.RLock()

            def outer(self):
                with self._mu:
                    self._inner()

            def _inner(self):
                with self._mu:
                    pass
    """
    assert run_lint(make_pkg(tmp_path, {"box.py": src}),
                    select={"LOCK02"}) == []


# -------------------------------------------------------------- BLK01

BLK_RPC_UNDER_LOCK = """
    import threading

    class Client:
        def __init__(self, rpc):
            self._mu = threading.Lock()
            self.rpc = rpc

        def fetch(self):
            with self._mu:
                return self.rpc.call_binary("get", {})
"""

BLK_RPC_OUTSIDE_LOCK = """
    import threading

    class Client:
        def __init__(self, rpc):
            self._mu = threading.Lock()
            self.rpc = rpc
            self.last = None

        def fetch(self):
            got = self.rpc.call_binary("get", {})
            with self._mu:
                self.last = got
            return got
"""


def test_blocking_rpc_under_lock_fires(tmp_path):
    diags = run_lint(make_pkg(tmp_path, {"c.py": BLK_RPC_UNDER_LOCK}),
                     select={"BLK01"})
    assert ids(diags) == ["BLK01"]
    assert "RPC" in diags[0].message and "Client._mu" in diags[0].message


def test_blocking_rpc_outside_lock_is_quiet(tmp_path):
    assert run_lint(make_pkg(tmp_path, {"c.py": BLK_RPC_OUTSIDE_LOCK}),
                    select={"BLK01"}) == []


def test_blocking_sleep_on_loop_thread_fires(tmp_path):
    src = """
        import time

        class RpcEventLoop:
            def _run(self):
                while True:
                    time.sleep(0.1)
    """
    diags = run_lint(make_pkg(tmp_path, {"loop.py": src}),
                     select={"BLK01"})
    assert ids(diags) == ["BLK01"]
    assert "time.sleep" in diags[0].message
    assert "RpcEventLoop" in diags[0].message


def test_blocking_done_cb_body_is_loop_reachable(tmp_path):
    src = """
        import time

        class Dispatch:
            def __init__(self, loop):
                self._loop = loop

            def go(self):
                self._loop.submit(
                    "ep", done_cb=lambda fut: self._settle(fut))

            def _settle(self, fut):
                time.sleep(1.0)
    """
    diags = run_lint(make_pkg(tmp_path, {"d.py": src}),
                     select={"BLK01"})
    assert ids(diags) == ["BLK01"]
    assert "time.sleep" in diags[0].message


def test_bounded_waits_under_lock_are_quiet(tmp_path):
    src = """
        import threading

        class Box:
            def __init__(self, q, t):
                self._mu = threading.Lock()
                self.q = q
                self.t = t

            def drain(self):
                with self._mu:
                    item = self.q.get(timeout=1.0)
                    self.t.join(5.0)
                    return item
    """
    assert run_lint(make_pkg(tmp_path, {"b.py": src}),
                    select={"BLK01"}) == []


def test_unbounded_join_under_lock_fires(tmp_path):
    src = """
        import threading

        class Box:
            def __init__(self, t):
                self._mu = threading.Lock()
                self.t = t

            def stop(self):
                with self._mu:
                    self.t.join()
    """
    diags = run_lint(make_pkg(tmp_path, {"b.py": src}),
                     select={"BLK01"})
    assert ids(diags) == ["BLK01"]
    assert "join" in diags[0].message


# -------------------------------------------------------------- JIT01

JIT_IMPURE = """
    import jax

    COUNTERS = None

    def build():
        def kern(x):
            COUNTERS.bump("kernel_calls")
            return x + 1
        return jax.vmap(kern)
"""

JIT_PURE = """
    import jax

    def build():
        def kern(x):
            return x + 1
        return jax.vmap(kern)
"""


def test_jit_purity_counter_bump_fires(tmp_path):
    diags = run_lint(make_pkg(tmp_path, {"k.py": JIT_IMPURE}),
                     select={"JIT01"})
    assert ids(diags) == ["JIT01"]
    assert "COUNTERS bump" in diags[0].message
    assert "trace time" in diags[0].message


def test_jit_purity_pure_kernel_is_quiet(tmp_path):
    assert run_lint(make_pkg(tmp_path, {"k.py": JIT_PURE}),
                    select={"JIT01"}) == []


def test_jit_purity_clock_read_via_jit_compile(tmp_path):
    src = """
        import time

        def build(cache):
            def kern(x):
                t0 = time.perf_counter()
                return x * t0
            return cache.jit_compile(kern)
    """
    diags = run_lint(make_pkg(tmp_path, {"k.py": src}),
                     select={"JIT01"})
    assert ids(diags) == ["JIT01"]
    assert "clock read" in diags[0].message


def test_jit_purity_covers_donated_accumulator_body(tmp_path):
    # the fused hot-loop shape: the accumulator-threading body handed
    # to jit_compile WITH donate_argnums is purity-checked exactly like
    # a plain traced body — donation kwargs must not hide it
    src = """
        COUNTERS = None

        def build(cache):
            def fused(acc, cols, valids, row_mask):
                COUNTERS.bump("fused_dispatches")
                return tuple(a + c for a, c in zip(acc, cols))
            return cache.jit_compile(fused, donate_argnums=0)
    """
    diags = run_lint(make_pkg(tmp_path, {"k.py": src}),
                     select={"JIT01"})
    assert ids(diags) == ["JIT01"]
    assert "COUNTERS bump" in diags[0].message

    pure = """
        def build(cache, xp):
            def fused(acc, cols, valids, row_mask):
                return tuple(xp.minimum(a, c) for a, c in zip(acc, cols))
            return cache.jit_compile(fused, donate_argnums=0)
    """
    assert run_lint(make_pkg(tmp_path, {"k.py": pure}),
                    select={"JIT01"}) == []


def test_new_rules_suppressible_with_pragma(tmp_path):
    src = """
        import threading

        class Client:
            def __init__(self, rpc):
                self._mu = threading.Lock()
                self.rpc = rpc

            def fetch(self):
                with self._mu:
                    # lint: disable=BLK01 -- single-writer socket, lock IS the wire serializer
                    return self.rpc.call_binary("get", {})
    """
    assert run_lint(make_pkg(tmp_path, {"c.py": src}),
                    select={"BLK01", "SUP01", "SUP02"}) == []
