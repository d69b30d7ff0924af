"""utils/lockdep: the runtime lock-order witness (PR 12) — graph
recording, online cycle detection, the witnessed-lock proxy (including
Condition wait rebalancing), the env-gated install path end-to-end in a
child process, and scripts/lockdep_check.py's verdicts."""

import json
import os
import pathlib
import subprocess
import sys
import threading

import pytest

from m3_tpu.utils.lockdep import LockdepGraph, _WitnessedLock

REPO = pathlib.Path(__file__).resolve().parent.parent


class TestGraph:
    def test_nested_acquire_records_innermost_edge(self):
        g = LockdepGraph()
        a, b, c = object(), object(), object()
        g.on_acquire("A", a, False, "x:1")
        g.on_acquire("B", b, False, "x:2")
        g.on_acquire("C", c, True, "x:3")
        assert ("A", "B") in g.edges
        assert ("B", "C") in g.edges
        assert ("A", "C") not in g.edges  # innermost-held only
        assert g.edges[("B", "C")]["blocked"] == 1
        g.on_release("C", c)
        g.on_release("B", b)
        g.on_release("A", a)
        assert g._held() == []

    def test_reentrant_same_object_records_nothing(self):
        g = LockdepGraph()
        a = object()
        g.on_acquire("A", a, False, "x:1")
        g.on_acquire("A", a, False, "x:2")
        assert g.edges == {}
        g.on_release("A", a)
        g.on_release("A", a)
        assert g._held() == []

    def test_abba_is_a_witnessed_cycle(self):
        g = LockdepGraph()
        a, b = object(), object()
        g.on_acquire("A", a, False, "t1:1")
        g.on_acquire("B", b, False, "t1:2")
        g.on_release("B", b)
        g.on_release("A", a)
        assert g.cycles == []
        g.on_acquire("B", b, False, "t2:1")
        g.on_acquire("A", a, True, "t2:2")
        assert len(g.cycles) == 1
        cyc = g.cycles[0]
        assert set(cyc) == {"A", "B"}

    def test_three_lock_cycle_detected(self):
        g = LockdepGraph()
        objs = {n: object() for n in "ABC"}

        def pair(x, y):
            g.on_acquire(x, objs[x], False, "s")
            g.on_acquire(y, objs[y], False, "s")
            g.on_release(y, objs[y])
            g.on_release(x, objs[x])

        pair("A", "B")
        pair("B", "C")
        assert g.cycles == []
        pair("C", "A")
        assert len(g.cycles) == 1

    def test_same_name_hierarchy_edge_is_not_a_cycle(self):
        # parent/child Enforcer chains: both locks are Enforcer._lock
        g = LockdepGraph()
        child, parent = object(), object()
        g.on_acquire("Enforcer._lock", child, False, "cost:1")
        g.on_acquire("Enforcer._lock", parent, False, "cost:2")
        assert g.cycles == []
        e = g.edges[("Enforcer._lock", "Enforcer._lock")]
        assert e["count"] == 1


class TestWitnessedLockProxy:
    def test_nesting_and_contention_flags(self):
        g = LockdepGraph()
        import m3_tpu.utils.lockdep as ld

        old = ld._GRAPH
        ld._GRAPH = g
        try:
            la = _WitnessedLock(threading.Lock(), "A")
            lb = _WitnessedLock(threading.Lock(), "B")
            with la:
                with lb:
                    pass
            assert ("A", "B") in g.edges
            assert not la.locked() and not lb.locked()
        finally:
            ld._GRAPH = old

    def test_condition_wait_rebalances_held_stack(self):
        g = LockdepGraph()
        import m3_tpu.utils.lockdep as ld

        old = ld._GRAPH
        ld._GRAPH = g
        try:
            mu = _WitnessedLock(threading.RLock(), "M")
            cond = threading.Condition(mu)
            hits = []

            def waiter():
                with cond:
                    hits.append("in")
                    cond.wait(timeout=5)
                    # stack must show M held again after wake
                    hits.append(tuple(n for n, _o in g._held()))

            t = threading.Thread(target=waiter)
            t.start()
            while "in" not in hits:
                pass
            with cond:
                cond.notify_all()
            t.join(5)
            assert not t.is_alive()
            assert hits[-1] == ("M",)
            # the main thread's stack drained too
            assert g._held() == []
        finally:
            ld._GRAPH = old


class TestEndToEnd:
    def test_env_gated_install_names_real_locks(self, tmp_path):
        """A child process with M3_TPU_LOCKDEP=1 exercising the real
        admission-gate/limits stack dumps a graph whose node names use
        the static Class.attr identity scheme."""
        code = (
            "import m3_tpu\n"
            "from m3_tpu.utils import lockdep\n"
            "assert lockdep.installed()\n"
            "from m3_tpu.utils.health import AdmissionGate\n"
            "g = AdmissionGate(8, name='')\n"
            "with g.held():\n"
            "    pass\n"
            "print(lockdep.dump_now())\n"
        )
        env = dict(os.environ, M3_TPU_LOCKDEP="1",
                   M3_TPU_LOCKDEP_OUT=str(tmp_path))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=str(REPO), capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr
        dumps = list(tmp_path.glob("lockdep-*.json"))
        assert dumps, out.stdout
        d = json.loads(dumps[0].read_text())
        assert "AdmissionGate._lock" in d["nodes"]
        assert d["cycles"] == []
        # admit under the gate lock bumps instrument counters: the
        # canonical cross-class edge must be witnessed and carry the
        # SAME identities the static graph uses
        pairs = {(e["from"], e["to"]) for e in d["edges"]}
        assert ("AdmissionGate._lock", "Scope._lock") in pairs

    def test_uninstalled_by_default(self):
        from m3_tpu.utils import lockdep

        if os.environ.get("M3_TPU_LOCKDEP", "") not in ("", "0"):
            pytest.skip("suite running under the witness")
        assert not lockdep.installed()
        assert type(threading.Lock()).__name__ in ("lock", "LockType")


def _run_check(tmp_path, dump):
    p = tmp_path / "lockdep-1.json"
    p.write_text(json.dumps(dump))
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lockdep_check.py"),
         str(tmp_path)],
        cwd=str(REPO), capture_output=True, text=True, timeout=300)


class TestLockdepCheck:
    BASE = {"pid": 1, "argv": ["x"], "time": 0.0, "nodes": {},
            "edges": [], "cycles": []}

    def test_green_on_statically_known_edge(self, tmp_path):
        d = dict(self.BASE)
        d["edges"] = [{"from": "Database._ns_lock",
                       "to": "HealthTracker._lock", "count": 1,
                       "blocked": 0, "site": "database.py:1"}]
        out = _run_check(tmp_path, d)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "GREEN" in out.stdout

    def test_cycle_fails_with_exit_2(self, tmp_path):
        d = dict(self.BASE)
        d["cycles"] = [["A._x", "B._y", "A._x"]]
        out = _run_check(tmp_path, d)
        assert out.returncode == 2
        assert "cycle" in out.stdout

    def test_unreconciled_edge_fails_with_exit_1(self, tmp_path):
        d = dict(self.BASE)
        d["edges"] = [{"from": "Nope._a", "to": "Nada._b", "count": 3,
                       "blocked": 1, "site": "zz.py:9"}]
        out = _run_check(tmp_path, d)
        assert out.returncode == 1
        assert "Nope._a -> Nada._b" in out.stdout

    def test_reconciled_edge_passes(self, tmp_path):
        # an entry actually present in the checked-in ledger
        d = dict(self.BASE)
        d["edges"] = [{"from": "InsertQueue._drain_mu",
                       "to": "Shard.write_lock",
                       "count": 2, "blocked": 0, "site": "shard.py:210"}]
        out = _run_check(tmp_path, d)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "reconciled (1)" in out.stdout


class TestBlockTimeWitness:
    def test_on_block_records_edge_before_park_and_flags_cycle(self):
        # a real deadlock never returns from the park: the edge (and the
        # cycle verdict) must exist BEFORE the blocking acquire
        g = LockdepGraph()
        a, b = object(), object()
        g.on_acquire("A", a, False, "t1:1")
        g.on_acquire("B", b, False, "t1:2")
        g.on_release("B", b)
        g.on_release("A", a)
        g.on_acquire("B", b, False, "t2:1")
        closed = g.on_block("A", a, "t2:2")
        assert closed is True
        assert ("B", "A") in g.edges
        assert g.edges[("B", "A")]["blocked"] == 1
        assert len(g.cycles) == 1

    def test_on_block_with_nothing_held_is_a_noop(self):
        g = LockdepGraph()
        assert g.on_block("A", object(), "s") is False
        assert g.edges == {}


class TestUnionCycle:
    def test_cross_process_abba_fails_exit_2(self, tmp_path):
        # write smoke witnesses A->B, churn smoke witnesses B->A: neither
        # process records a cycle online, only the union closes the loop
        base = {"pid": 1, "argv": ["x"], "time": 0.0, "nodes": {},
                "cycles": []}
        d1 = dict(base)
        d1["edges"] = [{"from": "Zed._a", "to": "Qux._b", "count": 1,
                        "blocked": 0, "site": "p1:1"}]
        d2 = dict(base)
        d2["edges"] = [{"from": "Qux._b", "to": "Zed._a", "count": 1,
                        "blocked": 1, "site": "p2:1"}]
        (tmp_path / "lockdep-1.json").write_text(json.dumps(d1))
        (tmp_path / "lockdep-2.json").write_text(json.dumps(d2))
        out = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "lockdep_check.py"),
             str(tmp_path)],
            cwd=str(REPO), capture_output=True, text=True, timeout=300)
        assert out.returncode == 2, out.stdout + out.stderr
        assert "union-of-dumps" in out.stdout


class TestDefiningClassNaming:
    def test_inherited_lock_named_by_defining_class(self, tmp_path):
        """FileStore inherits MemStore.__init__'s lock: the witness must
        name it MemStore._lock — the identity the static graph derives —
        not FileStore._lock (runtime subclass)."""
        code = (
            "import m3_tpu\n"
            "from m3_tpu.utils import lockdep\n"
            "from m3_tpu.cluster.kv import FileStore\n"
            "import tempfile, os\n"
            "s = FileStore(os.path.join(tempfile.mkdtemp(), 'kv.json'))\n"
            "print(lockdep.dump_now())\n"
        )
        env = dict(os.environ, M3_TPU_LOCKDEP="1",
                   M3_TPU_LOCKDEP_OUT=str(tmp_path))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=str(REPO), capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr
        d = json.loads(next(tmp_path.glob("lockdep-*.json")).read_text())
        assert "MemStore._lock" in d["nodes"], sorted(d["nodes"])
        assert "FileStore._lock" not in d["nodes"]
