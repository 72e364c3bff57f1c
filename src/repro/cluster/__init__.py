"""Cluster tier: heterogeneous serving fleets — simulated or live — behind
one ``NodeBackend`` interface (paper §VII — DeepRecSched deployed "on
hundreds of machines", validated against real execution).

* ``backend`` — the ``NodeBackend`` contract, the ``Completions`` batch
  every engine reports finished queries in, and ``SimNodeBackend``, the
  numpy fast engine.
* ``live`` / ``remote`` — real ``ServingRuntime``s (jitted JAX models,
  wall-clock pacing, device-curve calibration) in this process or in
  worker processes over localhost sockets, with the ``WorkerSupervisor``
  that spawns, health-checks and reaps them.
* ``fleet`` — ``NodeSpec``/``Pool``/``Fleet``: mixed CPU generations and
  accelerator nodes, each pool with its own DeepRecSched knobs.
* ``router`` — backend-agnostic routing policies; ``traffic`` — diurnal /
  bursty / multi-tenant arrival scenarios.
* ``lifecycle`` — ``FleetController`` owns node state (BOOTING → SERVING
  → DRAINING → DEAD, with a transient SUSPECT), ``FleetFaults`` kill
  plans and ``SelfHealPolicy``; ``chaos`` adds hung RPCs, garbled frames
  and slow starts (``ChaosPlan``).
* ``cluster_sim`` — ``drive_fleet``, one window loop over any backends,
  plus ``simulate_fleet`` and ``cluster_max_qps``.  Each opt-in layer is
  one stage the loop calls at fixed events: telemetry and SLO evaluation
  (``repro.obs``), the fleet-front cache (``cache.CacheStage`` over
  ``FleetCache``), online offload tuning (``OffloadTuning``) and
  autoscaling (``autoscaler.ScalingStage`` over ``Autoscaler``,
  ``PredictiveAutoscaler`` or ``DiagnosisPolicy``, with
  ``TelemetrySignal`` reading the registry's window sketches).
"""
from repro.cluster.autoscaler import (Autoscaler,  # noqa: F401
                                      CapacityLedger, DiagnosisPolicy,
                                      PredictiveAutoscaler, ScalingEvent,
                                      TelemetrySignal)
from repro.cluster.backend import (BackendDied,  # noqa: F401
                                   CompletedQuery, NodeBackend, NodeHandle,
                                   PendingQuery, SimNodeBackend, sim_backends)
from repro.cluster.cache import CacheConfig, FleetCache  # noqa: F401
from repro.cluster.chaos import (ChaosPlan, FrameGarble,  # noqa: F401
                                 RpcHang, SlowStart, crash_storm)
from repro.cluster.lifecycle import (FleetController,  # noqa: F401
                                     FleetFaults, LifecycleEvent, NodeKill,
                                     NodeState, SelfHealPolicy)
from repro.cluster.cluster_sim import (ClusterResult,  # noqa: F401
                                       OffloadTuning, cluster_max_qps,
                                       drive_fleet, simulate_fleet)
from repro.cluster.fleet import (Fleet, NodeSpec, Pool,  # noqa: F401
                                 ScaledDeviceModel)
from repro.cluster.live import (BucketedDeviceModel,  # noqa: F401
                                LiveNodeBackend, WallClock, calibrate_device,
                                live_node)
from repro.cluster.remote import (BootingRemoteBackend,  # noqa: F401
                                  RemoteBackendFactory, RemoteNodeBackend,
                                  RestartPolicy, WorkerCrashed,
                                  WorkerSupervisor, boot_remote_fleet,
                                  remote_node)
from repro.cluster.router import (HeterogeneityAwareRouter,  # noqa: F401
                                  LeastOutstandingRouter, RoundRobinRouter,
                                  Router, SizeAwareRouter, make_router)
from repro.cluster.traffic import (BurstyTraffic, DiurnalTraffic,  # noqa: F401
                                   MultiTenantTraffic, StationaryTraffic,
                                   Traffic)
