"""Trace-safety lint: each rule fires on a synthetic fixture, stays
silent on the compliant variant, honours ``# lint: allow(...)``, and the
real ``src/repro`` tree is clean (the CI contract)."""

import os
import textwrap

from repro.analysis.lint import lint_paths

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")


def _lint(tmp_path, source, name="mod.py"):
    path = tmp_path / name
    path.write_text(textwrap.dedent(source))
    return lint_paths([str(path)])


# ---------------------------------------------------------------------------
# L001: jit-reachable impurity
# ---------------------------------------------------------------------------


def test_l001_wall_clock_in_jitted_function(tmp_path):
    report = _lint(tmp_path, """
        import time
        import jax

        def forward(x):
            t = time.time()  # frozen at trace time
            return x * t

        fn = jax.jit(forward)
    """)
    assert report.rules() == {"L001"}
    assert "time.time" in report.errors[0].message


def test_l001_through_call_graph(tmp_path):
    report = _lint(tmp_path, """
        import time
        import numpy as np
        import jax

        def helper(x):
            return x + np.random.default_rng(0).random()

        def forward(x):
            return helper(x)

        fn = jax.jit(forward)
    """)
    assert report.rules() == {"L001"}
    assert "np.random" in report.errors[0].message


def test_l001_factory_closure_is_reachable(tmp_path):
    report = _lint(tmp_path, """
        import time
        import jax

        def make_step(cfg):
            def step(x):
                return x * time.perf_counter()
            return step

        fn = jax.jit(make_step(None))
    """)
    assert report.rules() == {"L001"}


def test_l001_decorated_seed(tmp_path):
    report = _lint(tmp_path, """
        import functools
        import time
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def forward(x, n):
            return x + time.monotonic()
    """)
    assert report.rules() == {"L001"}


def test_l001_unreachable_function_is_fine(tmp_path):
    report = _lint(tmp_path, """
        import time

        def host_only():
            return time.time()
    """)
    assert report.clean, report.format()


# ---------------------------------------------------------------------------
# L002: tracer defaults
# ---------------------------------------------------------------------------


def test_l002_tracer_without_default(tmp_path):
    report = _lint(tmp_path, """
        def compile_thing(x, tracer):
            return x
    """)
    assert report.rules() == {"L002"}


def test_l002_compliant_defaults(tmp_path):
    report = _lint(tmp_path, """
        from obs import NULL_TRACER

        def a(x, tracer=None):
            return x

        def b(x, tracer=NULL_TRACER):
            return x

        def _private(x, tracer):
            return x
    """)
    assert report.clean, report.format()


# ---------------------------------------------------------------------------
# L003: mutable defaults
# ---------------------------------------------------------------------------


def test_l003_mutable_literal_and_ctor(tmp_path):
    report = _lint(tmp_path, """
        def f(x, acc=[]):
            return acc

        def g(x, table=dict()):
            return table
    """)
    assert report.rules() == {"L003"}
    assert len(report.errors) == 2


def test_l003_nonfrozen_dataclass_default(tmp_path):
    report = _lint(tmp_path, """
        import dataclasses

        @dataclasses.dataclass
        class Mutable:
            n: int = 0

        @dataclasses.dataclass(frozen=True)
        class Frozen:
            n: int = 0

        def bad(cfg=Mutable()):
            return cfg

        def good(cfg=Frozen()):
            return cfg
    """)
    assert report.rules() == {"L003"}
    assert len(report.errors) == 1
    assert "Mutable" in report.errors[0].message


def test_l003_immutable_defaults_are_fine(tmp_path):
    report = _lint(tmp_path, """
        def f(x, pair=(1, 2), name="a", bits=frozenset({1})):
            return x
    """)
    assert report.clean, report.format()


# ---------------------------------------------------------------------------
# L004: unsynchronized timing
# ---------------------------------------------------------------------------


def test_l004_times_dispatch_not_execution(tmp_path):
    report = _lint(tmp_path, """
        import time
        import jax.numpy as jnp
        import jax

        def bench(x):
            t0 = time.perf_counter()
            y = jax.device_put(x)
            return time.perf_counter() - t0
    """)
    assert report.rules() == {"L004"}


def test_l004_block_until_ready_passes(tmp_path):
    report = _lint(tmp_path, """
        import time
        import jax

        def bench(x):
            t0 = time.perf_counter()
            y = jax.device_put(x)
            jax.block_until_ready(y)
            return time.perf_counter() - t0
    """)
    assert report.clean, report.format()


def test_l004_jax_work_outside_timed_region_passes(tmp_path):
    report = _lint(tmp_path, """
        import time
        import jax

        def bench(x):
            key = jax.random.PRNGKey(0)  # before the timed region
            t0 = time.time()
            host_work(key)
            return time.time() - t0
    """)
    assert report.clean, report.format()


# ---------------------------------------------------------------------------
# L006: lock discipline
# ---------------------------------------------------------------------------


def test_l006_unlocked_mutation_fires(tmp_path):
    report = _lint(tmp_path, """
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = {}
                self._count = 0

            def add(self, key, value):
                self._items[key] = value  # subscript store, unlocked
                self._count += 1          # augmented assign, unlocked
    """)
    assert report.rules() == {"L006"}
    assert len(report.diagnostics) == 2
    assert "Registry.add()" in report.diagnostics[0].message


def test_l006_locked_mutation_is_fine(tmp_path):
    report = _lint(tmp_path, """
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.RLock()
                self._items = {}

            def add(self, key, value):
                with self._lock:
                    self._items[key] = value

            def get(self, key):
                return self._items.get(key)  # reads are not flagged
    """)
    assert report.clean, report.format()


def test_l006_from_import_and_unlocked_delete(tmp_path):
    report = _lint(tmp_path, """
        from threading import Lock

        class Cache:
            def __init__(self):
                self._mu = Lock()
                self._data = {}

            def evict(self, key):
                del self._data[key]
    """)
    assert report.rules() == {"L006"}


def test_l006_lockless_class_and_init_are_exempt(tmp_path):
    report = _lint(tmp_path, """
        import threading

        class Plain:
            def __init__(self):
                self._items = {}

            def add(self, k, v):
                self._items[k] = v  # no lock attribute: not in scope

        class Locked:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = {}
                self._items["seed"] = 0  # __init__ is pre-publication
    """)
    assert report.clean, report.format()


def test_l006_nested_def_is_skipped(tmp_path):
    # a closure's execution context is unknown (it may run after the
    # lock is released, or under it) — neither flagged nor excused
    report = _lint(tmp_path, """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self._state = 0

            def schedule(self):
                def callback():
                    self._state = 1
                return callback
    """)
    assert report.clean, report.format()


def test_l006_allow_comment_suppresses(tmp_path):
    report = _lint(tmp_path, """
        import threading

        class Snapshot:
            def __init__(self):
                self._lock = threading.Lock()
                self._frozen = None

            def publish(self, value):
                self._frozen = value  # lint: allow(L006)
    """)
    assert report.clean, report.format()


# ---------------------------------------------------------------------------
# suppression + CLI + the real tree
# ---------------------------------------------------------------------------


def test_allow_comment_suppresses(tmp_path):
    report = _lint(tmp_path, """
        import time
        import jax

        def bench(x):
            t0 = time.time()  # lint: allow(L004)
            y = jax.device_put(x)
            return time.time() - t0
    """)
    assert report.clean, report.format()


def test_allow_comment_is_rule_specific(tmp_path):
    report = _lint(tmp_path, """
        import time
        import jax

        def bench(x):
            t0 = time.time()  # lint: allow(L001)
            y = jax.device_put(x)
            return time.time() - t0
    """)
    assert report.rules() == {"L004"}


def test_cli_lint(tmp_path, capsys):
    from repro.analysis.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text("def f(x, acc=[]):\n    return acc\n")
    assert main(["lint", str(bad)]) == 1
    assert "L003" in capsys.readouterr().out
    good = tmp_path / "good.py"
    good.write_text("def f(x):\n    return x\n")
    assert main(["lint", str(good)]) == 0


def test_repo_source_tree_is_clean():
    report = lint_paths([REPO_SRC])
    assert report.clean, report.format()


def test_repo_lint_actually_reaches_the_jitted_forward():
    # guard against the lint silently losing its seeds: the executor's
    # jitted forward and the pallas kernels must be in the reachable set
    from repro.analysis import lint as L

    mods = L._parse([REPO_SRC])
    by = {m.name: m for m in mods}
    for m in mods:
        for k in (m.name.removeprefix("repro."), m.name.split(".")[-1]):
            by.setdefault(k, m)
    seeds = L._collect_seeds(mods, by)
    reachable = L._reachable(mods, by, seeds)
    assert "repro.engine.executor::make_forward.forward" in seeds
    assert any(s.startswith("repro.kernels.pattern_spmm::") for s in seeds)
    assert len(reachable) >= len(seeds)
