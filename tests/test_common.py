"""Tests for ray_tpu.common: IDs, config, resources, task spec."""

import os
import pickle

import pytest

from ray_tpu.common.config import Config
from ray_tpu.common.ids import ActorID, JobID, NodeID, ObjectID, TaskID, WorkerID
from ray_tpu.common.resources import (
    CPU,
    TPU,
    LabelSelector,
    NodeResources,
    ResourceRequest,
    ResourceSet,
)
from ray_tpu.common.task_spec import FunctionDescriptor, TaskArg, TaskSpec, TaskType


class TestIds:
    def test_nesting(self):
        job = JobID.from_int(7)
        driver = TaskID.for_driver(job)
        assert driver.job_id() == job
        task = TaskID.for_normal_task(job, driver, 1)
        assert task.job_id() == job
        obj = ObjectID.from_index(task, 1)
        assert obj.task_id() == task
        assert obj.job_id() == job
        assert obj.index() == 1
        assert not obj.is_put()

    def test_put_objects(self):
        job = JobID.from_int(1)
        t = TaskID.for_driver(job)
        o = ObjectID.for_put(t, 3)
        assert o.is_put()
        assert o.task_id() == t

    def test_determinism(self):
        """Same (parent, index) -> same ID: the lineage-reconstruction invariant."""
        job = JobID.from_int(2)
        d = TaskID.for_driver(job)
        assert TaskID.for_normal_task(job, d, 5) == TaskID.for_normal_task(job, d, 5)
        assert TaskID.for_normal_task(job, d, 5) != TaskID.for_normal_task(job, d, 6)

    def test_actor_ids(self):
        job = JobID.from_int(3)
        d = TaskID.for_driver(job)
        a = ActorID.of(job, d, 0)
        assert a.job_id() == job
        ct = TaskID.for_actor_creation_task(a)
        assert ct.actor_id() == a
        mt = TaskID.for_actor_task(a, d, 1)
        assert mt.actor_id() == a

    def test_nil_and_random(self):
        assert NodeID.nil().is_nil()
        assert not NodeID.from_random().is_nil()
        assert NodeID.from_random() != NodeID.from_random()

    def test_pickle_roundtrip(self):
        w = WorkerID.from_random()
        assert pickle.loads(pickle.dumps(w)) == w

    def test_hex_roundtrip(self):
        n = NodeID.from_random()
        assert NodeID.from_hex(n.hex()) == n


class TestConfig:
    def test_default_and_system_config(self):
        c = Config()
        c.declare("foo_ms", int, 100)
        assert c.get("foo_ms") == 100
        c.initialize({"foo_ms": 250})
        assert c.get("foo_ms") == 250
        assert c.foo_ms == 250

    def test_env_override_wins(self):
        c = Config()
        c.declare("bar_enabled", bool, False)
        os.environ["RT_bar_enabled"] = "true"
        try:
            c.initialize({"bar_enabled": False})
            assert c.get("bar_enabled") is True
        finally:
            del os.environ["RT_bar_enabled"]

    def test_unknown_key_rejected(self):
        c = Config()
        with pytest.raises(ValueError):
            c.initialize({"nope": 1})
        with pytest.raises(KeyError):
            c.get("nope")

    @pytest.mark.parametrize("reader", ["finalizer", "other_thread"])
    def test_a_first_read_holds_no_lock(self, reader):
        """The collector may run while ``get`` reads a flag for the first
        time (the env lookup allocates), and what it finalizes reads a flag
        too: an ``ObjectRef`` frees through
        ``get("lineage_pinning_enabled")``, on the same thread and under
        the owner's other locks. That read has to return, and so has a
        first read on another thread that holds such a lock meanwhile."""
        import gc
        import threading

        c = Config()
        c.declare("inner", int, 7)
        seen = []

        class Ref:
            def __init__(self):
                self.cycle = self            # only the collector frees it

            def __del__(self):
                seen.append(c.get("inner"))

        def parse_inside_get(value):
            if reader == "finalizer":
                Ref()
                gc.collect()
            else:
                t = threading.Thread(
                    target=lambda: seen.append(c.get("inner")), daemon=True)
                t.start()
                t.join(5.0)
                assert not t.is_alive(), "a read waits for another read"
            return int(value)

        c.declare("outer", parse_inside_get, 0)
        c.initialize({"outer": 3})
        got = []
        t = threading.Thread(target=lambda: got.append(c.get("outer")),
                             daemon=True)
        t.start()
        t.join(10.0)
        assert not t.is_alive(), "a read waits for the read it runs inside"
        assert got == [3] and seen == [7]

    def test_a_write_is_seen_by_the_next_read(self):
        c = Config()
        c.declare("foo_ms", int, 100)
        assert c.get("foo_ms") == 100
        c.set_system_config_value("foo_ms", 5)
        assert c.get("foo_ms") == 5
        c.initialize({})
        assert c.get("foo_ms") == 100
        os.environ["RT_foo_ms"] = "9"
        try:
            assert c.get("foo_ms") == 100       # cached until reset
            c.reset_cache()
            assert c.get("foo_ms") == 9
        finally:
            del os.environ["RT_foo_ms"]


class TestResources:
    def test_fractional_exact(self):
        rs = ResourceSet({CPU: 0.1})
        total = ResourceSet({})
        for _ in range(10):
            total = total + rs
        assert total.get(CPU) == 1  # no float drift at 1e-4 resolution

    def test_subtract_underflow(self):
        a = ResourceSet({CPU: 1})
        with pytest.raises(ValueError):
            a - ResourceSet({CPU: 2})

    def test_node_allocate_free(self):
        node = NodeResources({CPU: 8, TPU: 4}, labels={"zone": "a"})
        req = ResourceRequest({CPU: 2, TPU: 2})
        assignment = node.allocate(req)
        assert assignment is not None
        assert sorted(assignment[TPU]) == [0, 1]
        assert node.available.get(TPU) == 2
        node.free(req, assignment)
        assert node.available.get(TPU) == 4
        # all chips whole again
        a2 = node.allocate(ResourceRequest({TPU: 4}))
        assert sorted(a2[TPU]) == [0, 1, 2, 3]

    def test_fractional_tpu(self):
        node = NodeResources({TPU: 2})
        a = node.allocate(ResourceRequest({TPU: 0.5}))
        b = node.allocate(ResourceRequest({TPU: 0.5}))
        assert a[TPU] == [0] and b[TPU] == [0]  # packed on one chip
        c = node.allocate(ResourceRequest({TPU: 1}))
        assert c[TPU] == [1]

    def test_fragmented_rollback_no_instance_leak(self):
        """A multi-resource request that fails on one resource must not leak
        instance slots picked for another (two-phase allocate)."""
        from ray_tpu.common.resources import GPU

        node = NodeResources({GPU: 1, TPU: 2})
        # fragment TPU chips: two allocations of 0.5 land on chip 0, then 0.7
        # forces chip 1 to fragment too
        node.allocate(ResourceRequest({TPU: 0.5}))
        node.allocate(ResourceRequest({TPU: 0.7}))
        # aggregate TPU available = 0.8+0.3 = 1.1 >= 1, but no whole chip free
        assert node.allocate(ResourceRequest({GPU: 1, TPU: 1})) is None
        # GPU must still be allocatable — no leaked zeroed slot
        a = node.allocate(ResourceRequest({GPU: 1}))
        assert a[GPU] == [0]

    def test_infeasible_vs_unavailable(self):
        node = NodeResources({CPU: 4})
        big = ResourceRequest({CPU: 8})
        small = ResourceRequest({CPU: 3})
        assert not node.is_feasible(big)
        assert node.is_feasible(small)
        node.allocate(small)
        assert node.is_feasible(small) and not node.is_available(small)

    def test_label_selector(self):
        sel = LabelSelector({"zone": "us-1", "tier": "!spot", "slice": "exists"})
        assert sel.matches({"zone": "us-1", "tier": "ondemand", "slice": "s0"})
        assert not sel.matches({"zone": "us-1", "tier": "spot", "slice": "s0"})
        assert not sel.matches({"zone": "us-1", "tier": "ondemand"})
        assert LabelSelector({"z": ["a", "b"]}).matches({"z": "b"})

    def test_snapshot_roundtrip(self):
        node = NodeResources({CPU: 8, TPU: 4}, labels={"k": "v"})
        node.allocate(ResourceRequest({CPU: 1}))
        snap = node.snapshot()
        restored = NodeResources.from_snapshot(snap)
        assert restored.available.get(CPU) == 7
        assert restored.labels == {"k": "v"}


class TestTaskSpec:
    def _spec(self):
        job = JobID.from_int(1)
        tid = TaskID.for_normal_task(job, TaskID.for_driver(job), 1)
        dep = ObjectID.for_put(TaskID.for_driver(job), 1)
        return TaskSpec(
            task_id=tid,
            job_id=job,
            task_type=TaskType.NORMAL_TASK,
            function=FunctionDescriptor("m", "f"),
            serialized_func=b"x",
            args=[TaskArg.inline(b"a"), TaskArg.by_ref(dep)],
            num_returns=2,
            required_resources=ResourceRequest({CPU: 1}),
        )

    def test_return_ids_deterministic(self):
        s = self._spec()
        rids = s.return_ids()
        assert len(rids) == 2
        assert rids[0].task_id() == s.task_id
        assert s.return_ids() == rids

    def test_dependencies(self):
        s = self._spec()
        deps = s.dependencies()
        assert len(deps) == 1

    def test_pickle(self):
        s = self._spec()
        s2 = pickle.loads(pickle.dumps(s))
        assert s2.task_id == s.task_id
        assert s2.required_resources.resources.get(CPU) == 1
