"""Elastic scale-up: workers join a running driver via join_driver.

The growth half of elasticity (the shrink half — worker death + requeue —
is tests/test_cluster.py): a driver starts with ZERO workers and an
elastic_listen endpoint; joiners dial in mid-run and the queued trials
dispatch to them. Workers run in-process threads here (join_driver serves
the same protocol the subprocess supervisor does, over its dialed socket).
"""

from __future__ import annotations

import os
import socket
import sys
import threading
import time

from distributed_machine_learning_tpu.tune.cluster import (
    join_driver,
    run_distributed,
)
from distributed_machine_learning_tpu.tune.trial import TrialStatus

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
if TESTS_DIR not in sys.path:
    sys.path.insert(0, TESTS_DIR)  # cluster_trainables resolves by name


def _listening_socket():
    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    server.bind(("127.0.0.1", 0))
    server.listen(8)
    return server, f"127.0.0.1:{server.getsockname()[1]}"


def test_workers_join_running_driver(tmp_path):
    server, addr = _listening_socket()
    result = {}

    def drive():
        result["analysis"] = run_distributed(
            "cluster_trainables:quadratic_trial",
            {"x": 2.0, "epochs": 2},
            metric="loss",
            workers=[],                      # zero capacity at start
            elastic_listen=server,
            num_samples=4,
            storage_path=str(tmp_path),
            verbose=0,
        )

    driver = threading.Thread(target=drive, daemon=True)
    driver.start()
    time.sleep(0.5)  # driver is up, waiting with no workers

    # Two workers join mid-run; each serves until the driver closes it.
    joiners = [
        threading.Thread(
            target=join_driver, args=(addr,), kwargs={"slots": 2}, daemon=True
        )
        for _ in range(2)
    ]
    for t in joiners:
        t.start()

    driver.join(timeout=120)
    assert not driver.is_alive(), "driver did not finish"
    analysis = result["analysis"]
    assert len(analysis.trials) == 4
    assert all(t.status == TrialStatus.TERMINATED for t in analysis.trials)
    assert all(t.training_iteration == 2 for t in analysis.trials)
    # join_driver returns when the driver disconnects it.
    for t in joiners:
        t.join(timeout=30)
        assert not t.is_alive(), "joiner did not return after driver teardown"


def test_join_adds_capacity_to_existing_pool(tmp_path, worker_env=None):
    """A dialed supervisor pool plus one elastic joiner both run trials."""
    from distributed_machine_learning_tpu.tune.cluster import start_local_workers

    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join(
            [TESTS_DIR]
            + [
                p
                for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                if p
            ]
        ),
    }
    procs, addrs = start_local_workers(1, slots=1, env=env)
    server, addr = _listening_socket()
    result = {}

    def drive():
        result["analysis"] = run_distributed(
            "cluster_trainables:quadratic_trial",
            {"x": 1.0, "epochs": 2},
            metric="loss",
            workers=addrs,
            elastic_listen=server,
            num_samples=6,
            storage_path=str(tmp_path),
            verbose=0,
        )

    driver = threading.Thread(target=drive, daemon=True)
    driver.start()
    time.sleep(0.3)
    joiner = threading.Thread(
        target=join_driver, args=(addr,), kwargs={"slots": 2}, daemon=True
    )
    joiner.start()
    driver.join(timeout=180)
    for p in procs:
        if p.poll() is None:
            p.terminate()
    assert not driver.is_alive(), "driver did not finish"
    analysis = result["analysis"]
    assert all(t.status == TrialStatus.TERMINATED for t in analysis.trials)
    # Both capacity sources actually ran trials.
    hosts = {
        r.get("hostname")
        for t in analysis.trials
        for r in t.results
    }
    assert len(analysis.trials) == 6
    assert hosts, "no hostnames recorded"
