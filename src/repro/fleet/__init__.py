"""Fault-tolerant multi-replica serving fleet.

``Fleet`` supervises N worker processes (each one serve replica), routes
requests by prefix affinity + load, detects crashed *and* wedged replicas
via main-loop heartbeats, and replays in-flight requests on healthy
replicas bit-exactly (greedy decode of ``prompt + emitted``).  See
:mod:`repro.fleet.supervisor` for the failure model and
:mod:`repro.fleet.faults` for the seeded fault-injection harness.
"""
from repro.fleet.faults import FaultInjector, FaultSpec, corrupt_lease_release
from repro.fleet.router import Router
from repro.fleet.supervisor import Fleet, FleetConfig, FleetRequest, WorkerStartupError
from repro.fleet.worker import ToyEngine, build_engine, toy_next_token, worker_main

__all__ = [
    "Fleet", "FleetConfig", "FleetRequest", "Router", "WorkerStartupError",
    "FaultInjector", "FaultSpec", "corrupt_lease_release",
    "ToyEngine", "build_engine", "toy_next_token", "worker_main",
]
