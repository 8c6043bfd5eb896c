"""One process per chip (core/chips.py): the parent-side check that turns a
hung or crashed child into a typed error before anything is launched."""

import pytest

from paddle_tpu.core import chips

TPU_ENV = {"PATH": "/usr/bin"}              # no JAX_PLATFORMS: wants the chip
CPU_ENV = {"JAX_PLATFORMS": "cpu"}


@pytest.fixture
def one_chip_host(monkeypatch):
    monkeypatch.setattr(chips, "local_chip_count", lambda: 1)
    monkeypatch.setattr(chips, "parent_holds_chip", lambda: False)


def test_cpu_children_are_never_limited(one_chip_host, monkeypatch):
    monkeypatch.setattr(chips, "parent_holds_chip", lambda: True)
    chips.check_spawn(8, CPU_ENV, "test")
    chips.check_spawn(8, {"JAX_PLATFORMS": "cpu,tpu"}, "test")


def test_host_without_accelerator_is_never_limited(monkeypatch):
    monkeypatch.setattr(chips, "local_chip_count", lambda: 0)
    chips.check_spawn(8, TPU_ENV, "test")


def test_one_child_per_accelerator_host(one_chip_host):
    chips.check_spawn(1, TPU_ENV, "test")
    with pytest.raises(chips.ChipContentionError, match="2 unpinned"):
        chips.check_spawn(2, TPU_ENV, "test")


def test_parent_that_holds_the_chip_cannot_spawn(one_chip_host, monkeypatch):
    monkeypatch.setattr(chips, "parent_holds_chip", lambda: True)
    with pytest.raises(chips.ChipContentionError, match="holds the host"):
        chips.check_spawn(1, TPU_ENV, "test")


def test_this_process_holds_no_chip():
    """The suite runs on the CPU backend: initialised, but not a chip."""
    import jax

    jax.devices()
    assert chips.parent_holds_chip() is False
    assert chips.children_use_cpu(CPU_ENV) and not chips.children_use_cpu({})


def test_cluster_refuses_subprocess_replicas_over_the_chips(one_chip_host):
    """A subprocess replica set larger than one process per accelerator
    host fails typed, before any replica is spawned."""
    from paddle_tpu.serving.cluster import ClusterController

    c = ClusterController("", replicas=2, inprocess=False,
                          replica_env=TPU_ENV, decode_model_dir="/nowhere")
    try:
        with pytest.raises(chips.ChipContentionError, match="inprocess"):
            c.start()
        assert c.replicas == []
    finally:
        # never started: there is no acceptor thread for close() to stop
        c.router_server._httpd.server_close()
        c.router.close()


def test_orchestrator_refuses_a_world_over_the_chips(one_chip_host):
    from paddle_tpu.distributed.launch import Orchestrator

    orch = Orchestrator(["true"], world=2, env=TPU_ENV)
    with pytest.raises(chips.ChipContentionError, match="2 unpinned"):
        orch.start()
    assert orch.trainers == []
