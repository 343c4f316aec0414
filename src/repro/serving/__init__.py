"""Serving: the deployed half of the split-policy system.

The canonical way to construct everything in this package is the
declarative deployment API::

    from repro.deploy import Deployment, DeploymentConfig

    dep = Deployment.build(DeploymentConfig.standard(k=4, c_in=12, h=84))
    params = dep.init(key)
    client, server = dep.serving_pair(params)   # EdgeClient + batching server

``DeploymentConfig`` names the encoder spec, input size, execution
backend, wire codec, head placement and micro-batching policy in one
frozen, JSON-serialisable manifest; ``Deployment.build`` resolves it into
the compiled PassPlan, the :class:`~repro.core.split.SplitModel`, and the
ready client/server pair below.  The classes in this package remain the
building blocks that Deployment assembles (and that tests/simulations
drive directly).

Module map
----------
``netsim``
    Deterministic bandwidth-shaped link (the ``tc netem`` stand-in):
    :class:`ShapedLink` serialises transfers FIFO with finite bandwidth,
    propagation delay and optional deterministic jitter.  Plus the
    scenario engine's adversarial family — :class:`TraceLink`
    (trace-driven piecewise bandwidth, integrated across regime
    boundaries), :class:`MarkovLink` (seeded Wi-Fi-style regime
    switching), :class:`LossyLink` (Bernoulli loss + RTO retransmit,
    head-of-line blocking), :class:`StochasticJitterLink` — every
    stochastic link replays bitwise from its seed on ``reset()``, and
    ``LINK_KINDS``/``make_link`` name link shapes for JSON schemas.
``profiles``
    The device zoo: :class:`DeviceProfile` names one hardware class
    (Jetson Nano / Pi 4B / Pi Zero 2W / workstation t(B) curves + encode
    cost); ``zoo`` cycles profiles across a fleet's servers.
``scenario``
    Named serving CONDITIONS: frozen, JSON-round-trippable
    :class:`Scenario` (seeded link + device zoo + client population +
    adaptation-mode ladder) in the ``SCENARIOS`` registry;
    :class:`ScenarioFleetSim` runs one through the fleet engine with a
    per-client adaptation controller (``"none"`` / ``"rule"`` /
    ``register_adaptation``) and scores latency, uplink bytes and the
    delivered-return proxy.  Drive from a manifest via
    ``Deployment.scenario_sim`` or ``python -m repro.deploy --scenario``;
    sweep via ``benchmarks/scenarios.py``.
``client``
    On-device half: :class:`EdgeClient` (the deployment's ``edge_fn`` —
    fused encoder + wire codec — with single and batched measurement) and
    :class:`DecisionLoop` (the paper's Figure-5 obs -> action pipeline for
    one client).
``server``
    Remote half: :class:`PolicyServer` (one request per call, the paper's
    FIFO baseline) and :class:`BatchingPolicyServer` (micro-batching: up
    to ``max_batch`` queued requests served by ONE batched call — the
    policy comes from ``DeploymentConfig.max_batch/max_wait_ms``; measures
    the t(B) service curve interpolated by :class:`BatchServiceModel`).
    Queueing simulators reproduce Table 6: :class:`QueueSim` (strict
    FIFO) and :class:`BatchQueueSim` (batch-aware — launches whatever has
    arrived when the server frees up, optionally holding ``max_wait_s``
    for the batch to fill).  Downlink accounting serialises: a batch of
    B actions charges B transfer slots on the return link, not one.
``fleet``
    Fleet scale: :class:`FleetQueueSim` shards the batch-aware
    simulation across ``n_servers`` micro-batching servers behind a
    pluggable router (``ROUTERS``: ``round_robin`` / ``least_loaded`` /
    ``client_affinity`` hash pinning), each with its own t(B) curve and
    serialised downlink, all fed from the shared shaped uplink.  Fleet
    sizing via ``max_clients`` (geometric + binary search) and
    ``min_servers``; ``n_servers=1`` reduces bitwise to
    :class:`BatchQueueSim`.
``realfleet``
    The fleet for REAL: :class:`RealFleet` spawns ``n_servers``
    continuous-batching :class:`WorkerServer` processes from one
    deployment manifest (localhost TCP, length-prefixed frames carrying
    the existing wire-codec payloads bitwise), fronted by
    :class:`FleetClient` — the SAME registered routers as the sim, plus
    per-request timeouts and re-routing retries.  ``run_load`` drives the
    Table 6 open-loop protocol against it so measured p95 can be
    calibrated against :class:`FleetQueueSim` predictions
    (``benchmarks/realfleet.py``).  Workers optionally token-bucket-shape
    request ingress (:class:`ShapingConfig` / :class:`TokenBucket`) — the
    measured counterpart of the sims' shaped uplink.  Construct via
    :meth:`repro.deploy.Deployment.fleet`.

The batched request path end-to-end: each client encodes ONE frame
(``Deployment.edge_fn`` / ``SplitModel.edge_step``), payloads are stacked
with ``repro.core.wire.stack_payloads`` (per-request quantisation headers
survive stacking), and the server decodes + projects the whole
micro-batch in one call (``Deployment.server_batch_fn`` /
``SplitModel.server_step_batch``).  A ``realfleet`` worker hands that call
the micro-batch as ONE host array: ``repro.core.wire.RowLayout`` packs
each request's leaves into one row of bytes, the block crosses to the
device as one buffer, and the jitted program unpacks it before the
server half runs.
"""
from repro.serving.netsim import (LINK_KINDS, LinkTrace, LossyLink,
                                  MarkovLink, ShapedLink,
                                  StochasticJitterLink, TraceLink,
                                  make_link, register_link_kind)
from repro.serving.server import (BatchingPolicyServer, BatchQueueSim,
                                  BatchServiceModel, PolicyServer, QueueSim)
from repro.serving.fleet import (FleetQueueSim, ROUTERS, get_router,
                                 register_router, router_names)
from repro.serving.client import EdgeClient, DecisionLoop
from repro.serving.profiles import (DEVICE_PROFILES, DeviceProfile,
                                    get_profile, register_profile, zoo)
from repro.serving.scenario import (ADAPTATIONS, SCENARIOS, AdaptationMode,
                                    Scenario, ScenarioFleetSim,
                                    ScenarioReport, get_adaptation,
                                    get_scenario, register_adaptation,
                                    register_scenario, scenario_names)
from repro.serving.realfleet import (FleetClient, FleetError, FleetTimeout,
                                     LoadReport, RealFleet, ShapingConfig,
                                     TokenBucket, WorkerServer,
                                     pack_payload, run_load, unpack_payload)

__all__ = ["ShapedLink", "LinkTrace", "TraceLink", "MarkovLink",
           "LossyLink", "StochasticJitterLink", "LINK_KINDS", "make_link",
           "register_link_kind", "PolicyServer", "BatchingPolicyServer",
           "BatchServiceModel", "BatchQueueSim", "QueueSim", "FleetQueueSim",
           "ROUTERS", "get_router", "register_router", "router_names",
           "EdgeClient", "DecisionLoop", "DeviceProfile", "DEVICE_PROFILES",
           "get_profile", "register_profile", "zoo", "Scenario",
           "SCENARIOS", "ScenarioFleetSim", "ScenarioReport",
           "AdaptationMode", "ADAPTATIONS", "register_scenario",
           "get_scenario", "scenario_names", "register_adaptation",
           "get_adaptation", "FleetClient", "FleetError", "FleetTimeout",
           "LoadReport", "RealFleet", "ShapingConfig", "TokenBucket",
           "WorkerServer", "pack_payload", "run_load", "unpack_payload"]
