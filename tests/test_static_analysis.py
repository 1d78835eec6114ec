"""ceph_tpu.analysis — the whole-tree concurrency + jit-boundary
static analyzer.

Per check family: one positive case (the check fires on a fixture
snippet) and one negative (clean idiom passes).  Plus the cycle
witness formatting, the suppression/baseline workflow, and the
tree-wide gate every future PR rides on: the real ``ceph_tpu``
package must produce ZERO unsuppressed findings.
"""

import os
import textwrap

import ceph_tpu
from ceph_tpu import analysis
from ceph_tpu.analysis import core, lock_order


def _tree(tmp_path, files: dict) -> core.TreeIndex:
    pkg = tmp_path / "pkg"
    pkg.mkdir(parents=True)
    for name, src in files.items():
        path = pkg / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return core.TreeIndex.build(str(pkg))


def _run(tmp_path, files, checks):
    pkg = tmp_path / "pkg"
    if not pkg.exists():
        _tree(tmp_path, files)
    return analysis.run(str(pkg), checks=checks)


# -- bare-lock ----------------------------------------------------------------

class TestBareLock:
    def test_fires_on_bare_locks(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import threading
            L = threading.Lock()
            class A:
                def __init__(self):
                    self.cv = threading.Condition()
            """}, checks=("bare-lock",))
        codes = sorted(f.code for f in rep.findings)
        assert codes == ["condition", "lock"]

    def test_clean_on_make_lock(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            from ceph_tpu.common import lockdep
            L = lockdep.make_lock("M::lock")
            CV = lockdep.make_condition("M::cv")
            """}, checks=("bare-lock",))
        assert rep.findings == []

    def test_inline_suppression(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import threading
            # analysis: allow[bare-lock] -- import-time leaf lock
            L = threading.Lock()
            """}, checks=("bare-lock",))
        assert rep.findings == []
        assert len(rep.suppressed) == 1
        assert rep.suppressed[0][1] == "import-time leaf lock"


# -- lock-order ---------------------------------------------------------------

_CYCLE_SRC = {"m.py": """
    import threading
    class A:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()
        def f(self):
            with self.a:
                self.helper()
        def helper(self):
            with self.b:
                pass
        def g(self):
            with self.b:
                with self.a:
                    pass
    """}


class TestLockOrder:
    def test_interprocedural_cycle_fires(self, tmp_path):
        rep = _run(tmp_path, _CYCLE_SRC, checks=("lock-order",))
        assert len(rep.findings) == 1
        f = rep.findings[0]
        # distinct cycles keep distinct baseline keys: the node set
        # rides the code
        assert f.code == "cycle:pkg.m.A.a+pkg.m.A.b"
        # both witness directions present, with file:line sites
        assert "pkg.m.A.a" in f.message and "pkg.m.A.b" in f.message
        assert f.message.count("m.py:") >= 2

    def test_consistent_order_clean(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import threading
            class A:
                def __init__(self):
                    self.a = threading.Lock()
                    self.b = threading.Lock()
                def f(self):
                    with self.a:
                        with self.b:
                            pass
                def g(self):
                    with self.a:
                        self.h()
                def h(self):
                    with self.b:
                        pass
            """}, checks=("lock-order",))
        assert rep.findings == []

    def test_runtime_graph_union(self, tmp_path):
        idx = _tree(tmp_path, {"m.py": """
            import threading
            class A:
                def __init__(self):
                    self.a = threading.Lock()
                    self.b = threading.Lock()
                def f(self):
                    with self.a:
                        with self.b:
                            pass
            """})
        # static a->b alone is clean; a runtime-recorded b->a closes
        # the cycle (the union the analyzer exists for)
        clean = lock_order.check(idx, runtime_graph=None)
        assert clean == []
        runtime = {"edges": [{"a": "pkg.m.A.b", "b": "pkg.m.A.a",
                              "site": "osd/daemon.py tick thread"}]}
        dirty = lock_order.check(idx, runtime_graph=runtime)
        assert len(dirty) == 1
        assert "runtime: osd/daemon.py tick thread" in dirty[0].message

    def test_deferred_closure_definition_is_not_a_hold_edge(
            self, tmp_path):
        """Defining a continuation under lock A whose body later takes
        B must NOT record A->B: the closure runs on another thread
        with an empty held stack (the engine's standard
        define-continuation-under-cv idiom).  A synchronously-CALLED
        local helper still propagates normally."""
        rep = _run(tmp_path, {"m.py": """
            import threading
            class A:
                def __init__(self):
                    self.a = threading.Lock()
                    self.b = threading.Lock()
                def deferred(self, fut):
                    with self.a:
                        def cont(f):
                            with self.b:
                                pass
                        fut.add_done_callback(cont)
                def other(self):
                    with self.b:
                        with self.a:
                            pass
            """}, checks=("lock-order",))
        assert rep.findings == []

        rep2 = _run(tmp_path / "sync", {"m.py": """
            import threading
            class A:
                def __init__(self):
                    self.a = threading.Lock()
                    self.b = threading.Lock()
                def f(self):
                    with self.a:
                        def h():
                            with self.b:
                                pass
                        h()            # called synchronously: a->b
                def other(self):
                    with self.b:
                        with self.a:
                            pass
            """}, checks=("lock-order",))
        assert len(rep2.findings) == 1

    def test_edge_suppression_breaks_cycle(self, tmp_path):
        src = dict(_CYCLE_SRC)
        src["m.py"] = src["m.py"].replace(
            "with self.b:\n                with self.a:",
            "with self.b:\n"
            "                # analysis: allow[lock-order] -- "
            "documented inversion\n"
            "                with self.a:")
        rep = _run(tmp_path, src, checks=("lock-order",))
        assert rep.findings == []

    def test_shared_condition_lock_aliases_one_node(self, tmp_path):
        """make_condition(name, lock=self._lock) shares ONE lock: an
        inversion through the condition must merge with the mutex's
        node, not hide behind a second name."""
        rep = _run(tmp_path, {"m.py": """
            from ceph_tpu.common import lockdep
            import threading
            class A:
                def __init__(self):
                    self.lk = lockdep.make_lock("A::lock")
                    self.cv = lockdep.make_condition("A::cv",
                                                     lock=self.lk)
                    self.b = threading.Lock()
                def f(self):
                    with self.lk:
                        with self.b:
                            pass
                def g(self):
                    with self.b:
                        with self.cv:
                            pass
            """}, checks=("lock-order",))
        assert len(rep.findings) == 1
        assert "A::lock" in rep.findings[0].message

    def test_cycle_witness_formatting(self):
        edges = {("X", "Y"): "a.py:10 in pkg.a.f",
                 ("Y", "X"): "runtime: b.py:20"}
        msg = lock_order.format_cycle(["X", "Y", "X"], edges)
        assert msg.startswith("lock-order cycle: ")
        assert "X -> Y  [a.py:10 in pkg.a.f]" in msg
        assert "Y -> X  [runtime: b.py:20]" in msg


# -- blocking -----------------------------------------------------------------

class TestBlocking:
    def test_fires_in_callback_reachable_code(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import time
            class E:
                def go(self, fut):
                    fut.add_done_callback(self.cb)
                def cb(self, f):
                    self.helper()
                def helper(self):
                    time.sleep(0.1)
                    w = self.make()
                    w.result()
                    self.lk.acquire(timeout=-1)   # block-forever
                    self.lk.acquire(timeout=2.0)  # bounded: exempt
            """}, checks=("blocking",))
        codes = sorted(f.code for f in rep.findings)
        assert codes == ["acquire", "future-wait", "sleep"]
        assert all("completion callback" in f.message
                   for f in rep.findings)

    def test_own_future_read_and_lock_section_clean(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            class E:
                def go(self, fut):
                    fut.add_done_callback(self.cb)
                def cb(self, f):
                    v = f.result()     # already complete: fine
                    with self.lock:    # bounded exclusion: fine
                        self.x = v
            """}, checks=("blocking",))
        assert rep.findings == []

    def test_attr_stored_future_wait_still_flagged(self, tmp_path):
        """The parameter exemption is for DIRECT parameter reads only:
        waiting on a future reached through `self` (create-then-wait
        on the completion thread) is the self-deadlock case."""
        rep = _run(tmp_path, {"m.py": """
            class E:
                def go(self, fut):
                    fut.add_done_callback(self.cb)
                def cb(self, f):
                    self._w = self.eng.submit(("k",), None, None)
                    self._w.result()
            """}, checks=("blocking",))
        assert [f.code for f in rep.findings] == ["future-wait"]

    def test_two_lambdas_one_line_both_scanned(self, tmp_path):
        """Two callbacks registered on one source line must get
        distinct nodes — a clean second lambda must not shadow the
        blocking first one."""
        rep = _run(tmp_path, {"m.py": """
            import time
            class E:
                def go(self, fa, fb):
                    fa.add_done_callback(lambda f: time.sleep(1)); fb.add_done_callback(lambda f: f.done())
            """}, checks=("blocking",))
        assert [f.code for f in rep.findings] == ["sleep"]

    def test_non_callback_code_not_flagged(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import time
            def plain():
                time.sleep(1)      # not on a completion path
            """}, checks=("blocking",))
        assert rep.findings == []


# -- jit-purity ---------------------------------------------------------------

class TestJitPurity:
    def test_fires_on_impure_jitted_fn(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import time, jax
            @jax.jit
            def k(x):
                t = time.time()
                print("tracing", t)
                return x
            """}, checks=("jit-purity",))
        codes = sorted(f.code for f in rep.findings)
        assert codes == ["clock", "logging"]

    def test_fires_on_engine_closure_mutation_and_conf(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            def submit_it(eng, ctx, data, state):
                def fn(batch):
                    state["n"] = 1
                    if ctx.conf.get("kernel_dispatch_depth"):
                        pass
                    return batch
                return eng.submit(("k",), fn, data)
            """}, checks=("jit-purity",))
        codes = sorted(f.code for f in rep.findings)
        assert codes == ["conf", "mutation"]
        assert "dispatch engine" in rep.findings[0].message

    def test_pure_kernel_clean(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import jax, jax.numpy as jnp
            @jax.jit
            def k(x):
                acc = {}
                acc["y"] = jnp.dot(x, x)   # local scaffolding: fine
                return acc["y"]
            """}, checks=("jit-purity",))
        assert rep.findings == []

    def test_placement_scaffolding_store_exempt(self, tmp_path):
        """The mesh-dispatch idiom: an engine closure caching a
        jax.device_put/NamedSharding placement into captured state is
        host-side sharding scaffolding (it runs on the engine thread
        outside any trace), NOT a tracer leak — no mutation finding."""
        rep = _run(tmp_path, {"m.py": """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            def submit_it(eng, data, tables, ids):
                def fn(batch):
                    mesh = batch.sharding.mesh
                    ops = tables.get("ops")
                    if ops is None:
                        ops = tables["ops"] = jax.device_put(
                            ids, NamedSharding(mesh, PartitionSpec()))
                    return ops
                return eng.submit(("k",), fn, data)
            """}, checks=("jit-purity",))
        assert rep.findings == []

    def test_non_placement_store_still_fires(self, tmp_path):
        """The exemption is scoped to placement construction: the same
        captured-store shape WITHOUT device_put/NamedSharding on the
        right-hand side stays a mutation finding."""
        rep = _run(tmp_path, {"m.py": """
            def submit_it(eng, data, tables, ids):
                def fn(batch):
                    ops = tables.get("ops")
                    if ops is None:
                        ops = tables["ops"] = (ids, batch.shape)
                    return ops
                return eng.submit(("k",), fn, data)
            """}, checks=("jit-purity",))
        codes = sorted(f.code for f in rep.findings)
        assert codes == ["mutation"]

    def test_jit_traced_placement_store_still_fires(self, tmp_path):
        """The exemption is scoped to engine submit closures: inside a
        function genuinely TRACED by jax.jit the same device_put store
        runs once at trace time and never on cache hits — it stays a
        mutation finding."""
        rep = _run(tmp_path, {"m.py": """
            import jax
            @jax.jit
            def k(x, cache):
                cache["dev"] = jax.device_put(x)
                return x
            """}, checks=("jit-purity",))
        codes = sorted(f.code for f in rep.findings)
        assert codes == ["mutation"]

    def test_compound_rhs_with_placement_still_fires(self, tmp_path):
        """The exemption covers stores whose WHOLE value is placement
        construction: a compound RHS smuggling other state next to a
        device_put stays a mutation finding."""
        rep = _run(tmp_path, {"m.py": """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            def submit_it(eng, data, tables, ids):
                def fn(batch):
                    if "ops" not in tables:
                        tables["ops"] = (batch.sum(), jax.device_put(
                            ids, NamedSharding(batch.sharding.mesh,
                                               PartitionSpec())))
                    return tables["ops"]
                return eng.submit(("k",), fn, data)
            """}, checks=("jit-purity",))
        codes = sorted(f.code for f in rep.findings)
        assert codes == ["mutation"]


# -- registry -----------------------------------------------------------------

class TestRegistry:
    FILES = {
        "config.py": """
            class Option:
                def __init__(self, name, *a, **k):
                    self.name = name
            OPTIONS = {}
            def register_options(opts):
                pass
            register_options([Option("real_option", "int", 1)])
            """,
        "perf.py": """
            class PerfCountersBuilder:
                def __init__(self, name): ...
            def build():
                return (PerfCountersBuilder("osd")
                        .add_u64("real_counter")
                        .create_perf_counters())
            """,
        "user.py": """
            def f(ctx, perf):
                ctx.conf.get("real_option")
                ctx.conf.get("typo_option")
                perf.inc("real_counter")
                perf.inc("typo_counter")
            """,
    }

    def test_fires_on_unknown_key_and_counter(self, tmp_path):
        rep = _run(tmp_path, self.FILES, checks=("registry",))
        assert sorted(f.code for f in rep.findings) == \
            ["conf-key", "perf-counter"]
        assert "typo_option" in rep.findings[0].message
        assert "typo_counter" in rep.findings[1].message

    def test_known_names_clean(self, tmp_path):
        files = dict(self.FILES)
        files["user.py"] = """
            def f(ctx, perf):
                ctx.conf.get("real_option")
                perf.inc("real_counter")
            """
        rep = _run(tmp_path, files, checks=("registry",))
        assert rep.findings == []


# -- thread-except ------------------------------------------------------------

class TestThreadExcept:
    def test_fires_on_swallowed_base_exception(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import threading

            def loop():
                while True:
                    try:
                        step()
                    except BaseException:
                        pass

            def step():
                return 1

            def start():
                threading.Thread(target=loop, daemon=True).start()
            """}, checks=("thread-except",))
        assert [f.code for f in rep.findings] == ["swallow"]
        assert "loop" in rep.findings[0].message

    def test_fires_on_bare_except_in_thread_subclass_run(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import threading

            class Worker(threading.Thread):
                def run(self):
                    while True:
                        try:
                            self.step()
                        except:
                            continue

                def step(self):
                    return 1
            """}, checks=("thread-except",))
        assert [f.code for f in rep.findings] == ["swallow"]

    def test_fires_through_call_graph(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import threading

            def loop():
                helper()

            def helper():
                try:
                    work()
                except BaseException as e:
                    del e      # bound but never READ: still swallowed

            def work():
                return 1

            def start():
                threading.Thread(target=loop).start()
            """}, checks=("thread-except",))
        assert [f.code for f in rep.findings] == ["swallow"]

    def test_delivering_and_reraising_handlers_clean(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import threading

            def loop():
                exc = None
                while True:
                    try:
                        step()
                    except BaseException as e:
                        exc = e          # delivered to the waiter
                    try:
                        step()
                    except BaseException:
                        raise            # re-raised to the supervisor
                    try:
                        step()
                    except ValueError:
                        pass             # narrow catch: normal absorb
                    try:
                        step()
                    except Exception:
                        continue         # Exception (not Base): fine
                return exc

            def step():
                return 1

            def start():
                threading.Thread(target=loop).start()
            """}, checks=("thread-except",))
        assert rep.findings == []

    def test_not_flagged_outside_thread_paths(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            def plain_helper():
                try:
                    work()
                except BaseException:
                    pass       # not reachable from any thread body

            def work():
                return 1
            """}, checks=("thread-except",))
        assert rep.findings == []

    def test_inline_suppression(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import threading

            def loop():
                try:
                    step()
                except BaseException:  # analysis: allow[thread-except] -- fixture
                    pass

            def step():
                return 1

            def start():
                threading.Thread(target=loop).start()
            """}, checks=("thread-except",))
        assert rep.findings == []
        assert len(rep.suppressed) == 1


# -- baseline workflow --------------------------------------------------------

class TestBaseline:
    def test_diff_and_roundtrip(self, tmp_path):
        rep = _run(tmp_path, {"m.py": """
            import threading
            L = threading.Lock()
            """}, checks=("bare-lock",))
        assert len(rep.findings) == 1
        path = str(tmp_path / "baseline.txt")
        analysis.save_baseline(path, rep.findings)
        baseline = analysis.load_baseline(path)
        new, stale = analysis.diff_baseline(rep, baseline)
        assert new == [] and stale == []
        # a fixed finding becomes a stale entry; a fresh one is new
        empty = analysis.Report()
        new, stale = analysis.diff_baseline(empty, baseline)
        assert new == [] and len(stale) == 1
        new, stale = analysis.diff_baseline(rep, set())
        assert len(new) == 1 and stale == []

    def test_cli_json_and_exit_codes(self, tmp_path, capsys):
        import json
        from ceph_tpu.analysis.__main__ import main
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "m.py").write_text("import threading\n"
                                  "L = threading.Lock()\n")
        bl = str(tmp_path / "bl.txt")
        rc = main([str(pkg), "--json", "--baseline", bl,
                   "--checks", "bare-lock"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1 and out["exit"] == 1
        assert out["findings"][0]["check"] == "bare-lock"
        # accept into the baseline -> clean run exits 0
        assert main([str(pkg), "--write-baseline", "--baseline", bl,
                     "--checks", "bare-lock"]) == 0
        capsys.readouterr()
        assert main([str(pkg), "--baseline", bl,
                     "--checks", "bare-lock"]) == 0


# -- the tree-wide gate -------------------------------------------------------

class TestTreeGate:
    def test_ceph_tpu_is_clean(self):
        """THE gate: the real package, every check, zero unsuppressed
        findings beyond the checked-in baseline (kept empty).  A new
        finding here means fix it or justify an inline suppression —
        see docs/STATIC_ANALYSIS.md."""
        root = os.path.dirname(os.path.abspath(ceph_tpu.__file__))
        rep = analysis.run(root)
        baseline = analysis.load_baseline(
            analysis.default_baseline_path())
        new, _stale = analysis.diff_baseline(rep, baseline)
        assert new == [], (
            "new static-analysis findings:\n"
            + "\n".join(f.render() for f in new))

    def test_every_family_has_runtime_coverage(self):
        """The gate is only meaningful if the checks have real targets
        in this tree: assert the fact extraction still sees jit
        targets, completion callbacks, named locks, and the option
        table (a refactor that silently blinds a check family would
        otherwise pass the gate forever)."""
        from ceph_tpu.analysis import blocking, jit_purity, \
            registry_lint, thread_except
        root = os.path.dirname(os.path.abspath(ceph_tpu.__file__))
        idx = core.TreeIndex.build(root)
        assert len(jit_purity._targets(idx)) >= 4
        assert len(blocking._roots(idx)) >= 3
        # thread run-loop roots: the supervised engine loops, the
        # probe loop, daemon threads, Thread-subclass run()s
        assert len(thread_except._thread_roots(idx)) >= 4
        edges = lock_order.build_graph(idx)
        assert len(edges) >= 10
        assert "osdmap_mapping_min_pgs" in \
            registry_lint._option_names(idx)
        assert "ec_dispatch_submits" in \
            registry_lint._registered_counters(idx)
