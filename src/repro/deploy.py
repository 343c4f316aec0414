"""repro.deploy — ONE declarative deployment API: encoder spec -> served policy.

The paper's artifact is a deployment *pipeline*: a
:class:`~repro.core.miniconv.MiniConvSpec` is compiled into an ordered
shader-pass schedule (:class:`~repro.core.passplan.PassPlan`), executed by
a backend (fragment shaders on the Pi, Pallas kernels here), split at the
wire boundary (:class:`~repro.core.split.SplitModel` +
:class:`~repro.core.wire.WireCodec`) and served to clients
(:class:`~repro.serving.server.BatchingPolicyServer` /
:class:`~repro.serving.client.EdgeClient`).  This module makes that whole
pipeline ONE object:

* :class:`DeploymentConfig` — a frozen, JSON-serialisable manifest of the
  deployment: the spec, the concrete input size, the execution backend
  (``repro.core.backends`` registry), the micro-batching policy, the wire
  codec and the head placement.  ``to_dict``/``from_dict`` round-trip, so
  a manifest can ship to the device exactly like the paper's compiled
  shader bundles.
* :class:`Deployment` — the compiled form.  ``Deployment.build(config)``
  resolves the config ONCE into the budget-checked PassPlan (including
  the batch-size-aware VMEM check), parameter initialisers, the
  :class:`SplitModel`, the RL-facing :class:`~repro.rl.networks.Encoder`,
  the codec, and factories for a ready ``EdgeClient`` /
  ``BatchingPolicyServer`` pair.

Two encoder families deploy: MiniConv (:class:`~repro.core.miniconv.
MiniConvSpec`), split at its feature map, on any backend; and IMPALA-CNN
(:class:`~repro.core.impala.ImpalaSpec`), server-only on ``xla``: the
edge half is the identity, the codec carries the frame itself, and the
server runs torso and head.

Every entry point — training (``repro.rl.train``), serving, and the
benchmarks — constructs the pipeline through ``Deployment.build``; the
legacy constructors (``rl.networks.make_encoder``,
``core.split.make_miniconv_split``) survive as thin deprecation shims
over it.

Quick start::

    from repro.deploy import Deployment, DeploymentConfig

    cfg = DeploymentConfig.standard(k=4, c_in=12, h=84, backend="fused")
    dep = Deployment.build(cfg)
    params = dep.init(jax.random.PRNGKey(0))
    client = dep.client(params)            # EdgeClient: obs -> payload
    server = dep.server(params)            # BatchingPolicyServer
    actions = server.serve([client.encode_fn(obs)])

Run ``python -m repro.deploy`` to write (and round-trip-verify) a
deployment manifest.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import impala
from repro.core.backends import ExecutionBackend, backend_names, get_backend
from repro.core.impala import ImpalaSpec
from repro.core.miniconv import (_ACTS, LayerSpec, MiniConvSpec,
                                 ShaderBudget, miniconv_apply, standard_spec)
from repro.core.passplan import HeadPlan, PassPlan, build_pass_plan
from repro.core.split import SplitModel
from repro.schema import check_version
from repro.core.tuning import TunedPlan
from repro.core.wire import CODECS, WireCodec, get_codec
from repro.kernels.interpret import resolve_interpret
from repro.nn.layers import dense
from repro.rl.networks import Encoder, miniconv_encoder_init
from repro.serving.client import EdgeClient
from repro.serving.fleet import ROUTERS, FleetQueueSim
from repro.serving.server import BatchingPolicyServer
from repro.tracing import scope

# version 2 added the optional ``tuning`` block (a frozen TunedPlan);
# version-1 manifests load unchanged with ``tuning=None``.  Version 3 tags
# the spec with its encoder ``family``; older manifests are MiniConv.
CONFIG_VERSION = 3
_READABLE_VERSIONS = (1, 2, 3)
_FAMILIES = ("miniconv", "impala")


# ---------------------------------------------------------------------------
# The manifest
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeploymentConfig:
    """Declarative, serialisable description of one split-policy deployment.

    Fields
    ------
    spec            : the encoder architecture: a budget-checked
                      :class:`MiniConvSpec`, or an :class:`ImpalaSpec`
                      (server-only, ``xla`` backend only).
    in_h, in_w      : the concrete input size the edge device sees.
    backend         : execution-backend name (``repro.core.backends``):
                      ``xla`` | ``reference`` | ``grouped`` | ``fused`` |
                      ``fused+head``.
    interpret       : Pallas interpret (True) vs compiled (False) for the
                      kernel backends; ``None`` = auto (interpreted on the
                      CPU backend, compiled on the chip —
                      ``repro.kernels.interpret.resolve_interpret``).
    codec           : wire-codec name (``repro.core.wire.CODECS``).
    head_dim        : width of the server-side projection (paper: 512).
    head_act        : activation of the projection.
    head_placement  : ``"server"`` — projection runs as the server half of
                      the split (the paper's deployment); ``"fused"`` —
                      projection is fused with the encoder into one call
                      (one kernel launch under the ``fused`` backends; the
                      colocated training / replay-encoding hot path).
    max_batch       : server micro-batching cap (B frames per launch).
    max_wait_ms     : how long the server holds a batch open for
                      stragglers.
    tile_h          : fused-kernel output-row tile height.
    quantize_in_train : straight-through-quantise features during training
                      so training numerics match the deployed wire.
    n_servers       : fleet size — how many independent micro-batching
                      servers share the ingress (1 = the paper's Table 6
                      single server).
    router          : fleet routing policy (``repro.serving.fleet.ROUTERS``):
                      ``round_robin`` | ``least_loaded`` |
                      ``client_affinity`` (hash-pinned, keeps one client's
                      requests ordered).
    tuning          : optional frozen :class:`~repro.core.tuning.TunedPlan`
                      (``core.tuning.tune`` / ``python -m repro.deploy
                      --tune``).  When present, :meth:`Deployment.build`
                      executes with the tuned backend / ``tile_h`` /
                      micro-batch instead of the fields above — tune once,
                      freeze into the manifest, every entry point inherits
                      the tuned kernels.
    """

    spec: MiniConvSpec | ImpalaSpec
    in_h: int
    in_w: int
    backend: str = "fused"
    interpret: Optional[bool] = None
    codec: str = "uint8"
    head_dim: int = 512
    head_act: str = "relu"
    head_placement: str = "server"
    max_batch: int = 8
    max_wait_ms: float = 0.0
    tile_h: int = 8
    quantize_in_train: bool = False
    n_servers: int = 1
    router: str = "round_robin"
    tuning: Optional[TunedPlan] = None

    def __post_init__(self):
        # canonicalise backend aliases (and the legacy use_kernel booleans)
        # at construction so equality and serialisation are name-stable
        object.__setattr__(self, "backend", get_backend(self.backend).name)
        if isinstance(self.tuning, dict):     # deserialised manifests
            object.__setattr__(self, "tuning",
                               TunedPlan.from_dict(self.tuning))

    # ---- construction helpers ---------------------------------------------
    @classmethod
    def standard(cls, *, k: int = 4, c_in: int = 12, h: int = 84,
                 w: Optional[int] = None, **overrides) -> "DeploymentConfig":
        """The paper's standard encoder family, deployed at (h, w)."""
        return cls(spec=standard_spec(c_in=c_in, k=k), in_h=h,
                   in_w=h if w is None else w, **overrides)

    @classmethod
    def from_encoder_name(cls, name: str, *, c_in: int, h: int = 84,
                          w: Optional[int] = None,
                          **overrides) -> "DeploymentConfig":
        """``miniconv<K>`` (the ``rl.networks.make_encoder`` names), or
        ``impala``: IMPALA-CNN at Procgen's widths, server-only on
        ``xla`` with its 256-wide head unless ``overrides`` say else."""
        if name == "impala":
            overrides = {"backend": "xla", "head_dim": 256, **overrides}
            return cls(spec=ImpalaSpec(c_in=c_in), in_h=h,
                       in_w=h if w is None else w, **overrides)
        if not name.startswith("miniconv"):
            raise ValueError(f"no deployment for encoder {name!r}: one of "
                             f"miniconv<K>, impala (full_cnn has no split "
                             f"pipeline)")
        k = int(name.replace("miniconv", ""))
        return cls.standard(k=k, c_in=c_in, h=h, w=w, **overrides)

    @property
    def family(self) -> str:
        """The encoder family: ``miniconv`` or ``impala``."""
        return "impala" if isinstance(self.spec, ImpalaSpec) else "miniconv"

    # ---- validation --------------------------------------------------------
    def validate(self) -> None:
        get_backend(self.backend)          # raises listing registered names
        if self.family == "impala":
            if self.backend != "xla" or self.tuning is not None:
                raise ValueError(
                    f"the impala family runs on the xla backend only, "
                    f"untuned; got backend {self.backend!r}"
                    f"{' with a tuning block' if self.tuning else ''}")
            if self.head_placement != "server":
                raise ValueError(
                    f"the impala family deploys server-only: "
                    f"head_placement must be 'server', got "
                    f"{self.head_placement!r}")
        if self.codec not in CODECS:
            raise ValueError(f"unknown codec {self.codec!r}; registered: "
                             f"{', '.join(CODECS)}")
        if self.head_placement not in ("server", "fused"):
            raise ValueError(f"head_placement must be 'server' or 'fused', "
                             f"got {self.head_placement!r}")
        if self.head_act not in _ACTS:
            raise ValueError(f"unknown head_act {self.head_act!r}; one of "
                             f"{', '.join(_ACTS)}")
        if self.in_h < 1 or self.in_w < 1:
            raise ValueError(f"input size must be positive, got "
                             f"{(self.in_h, self.in_w)}")
        if self.head_dim < 1:
            raise ValueError(f"head_dim must be positive: {self.head_dim}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1: {self.max_batch}")
        if self.max_wait_ms < 0:
            raise ValueError(f"max_wait_ms must be >= 0: {self.max_wait_ms}")
        if self.tile_h < 1:
            raise ValueError(f"tile_h must be >= 1: {self.tile_h}")
        if self.n_servers < 1:
            raise ValueError(f"n_servers must be >= 1: {self.n_servers}")
        if self.router not in ROUTERS:
            raise ValueError(f"unknown router {self.router!r}; registered: "
                             f"{', '.join(ROUTERS)}")
        if self.tuning is not None:
            get_backend(self.tuning.backend)   # raises listing names
            if self.tuning.tile_h < 1 or self.tuning.micro_batch < 1:
                raise ValueError(
                    f"tuning tile_h/micro_batch must be >= 1, got "
                    f"{self.tuning.tile_h}/{self.tuning.micro_batch}")
        self.spec.validate()

    # ---- serialisation -----------------------------------------------------
    def to_dict(self) -> dict:
        """JSON-safe manifest; inverse of :meth:`from_dict`."""
        d = dataclasses.asdict(self)
        if self.family == "impala":
            d["spec"] = {"family": "impala",
                         **dataclasses.asdict(self.spec)}
        else:
            d["spec"] = {
                "family": "miniconv",
                "layers": [dataclasses.asdict(l) for l in self.spec.layers],
                "budget": dataclasses.asdict(self.spec.budget),
            }
        d["tuning"] = None if self.tuning is None else self.tuning.to_dict()
        d["version"] = CONFIG_VERSION
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "DeploymentConfig":
        d = dict(d)
        check_version("DeploymentConfig manifest",
                      d.pop("version", CONFIG_VERSION), _READABLE_VERSIONS)
        s = dict(d.pop("spec"))
        family = s.pop("family", "miniconv")
        if family == "impala":
            spec = ImpalaSpec(**s)
        elif family == "miniconv":
            spec = MiniConvSpec(
                layers=tuple(LayerSpec(**l) for l in s["layers"]),
                budget=ShaderBudget(**s.get("budget", {})))
        else:
            raise ValueError(f"unknown encoder family {family!r}; one of "
                             f"{', '.join(_FAMILIES)}")
        # pre-tuning (version-1) manifests default cleanly to tuning=None;
        # __post_init__ revives a serialised TunedPlan dict
        return cls(spec=spec, **d)

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)

    @classmethod
    def from_json(cls, s: str) -> "DeploymentConfig":
        return cls.from_dict(json.loads(s))


# ---------------------------------------------------------------------------
# The compiled deployment
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Deployment:
    """A resolved deployment: every pipeline stage, built once from config.

    Construct with :meth:`build`.  The object is cheap to carry around —
    parameters stay OUTSIDE (functional style), so one Deployment serves
    training, serving, and benchmarking with different parameter sets.
    """

    config: DeploymentConfig
    backend: ExecutionBackend
    plan: Optional[PassPlan]            # None for the impala family
    head_plan: Optional[HeadPlan]       # None for the impala family
    codec: WireCodec
    split: SplitModel
    encoder: Encoder
    max_safe_batch: int
    tile_h: int = 8
    stream_chunk: Optional[int] = None
    compiled: bool = False
    build_log: tuple = ()

    # ---- the compiler ------------------------------------------------------
    @classmethod
    def build(cls, config: DeploymentConfig) -> "Deployment":
        """Resolve ``config`` into the executable pipeline.

        The PassPlan is lowered and shader-budget-checked once, up front.
        A manifest ``tuning`` block overrides the executed backend /
        ``tile_h`` / micro-batch (tune once, serve everywhere).  When the
        resolved backend runs the fused Pallas kernel compiled
        (``interpret=False``, or ``interpret=None`` on an accelerator),
        the configured micro-batch is checked against the fused kernel's
        VMEM residency model — and an over-budget batch is no longer
        rejected: it is PIPELINED through :func:`~repro.kernels.
        miniconv_pass.miniconv_encoder_stream` in chunks that fit the
        streamed launch (the decision is recorded in ``build_log``).
        Build still fails, with the computed ``max_safe_batch`` and the
        tuner's suggestion, when even a single frame exceeds the budget.

        An :class:`ImpalaSpec` builds the server-only placement
        (:meth:`_build_impala`).
        """
        config.validate()
        if config.family == "impala":
            return cls._build_impala(config)
        backend = get_backend(config.backend)
        tile_h = config.tile_h
        tuning = config.tuning
        log: list[str] = []
        if tuning is not None:
            backend = get_backend(tuning.backend)
            tile_h = tuning.tile_h
            log.append(
                f"tuning: manifest TunedPlan -> backend={backend.name} "
                f"tile_h={tile_h} micro_batch={tuning.micro_batch} "
                f"(measured {tuning.mode} on {tuning.host or 'unknown'})")
        spec = config.spec
        plan = build_pass_plan(spec, config.in_h, config.in_w)
        head_plan = plan.head(config.head_dim, activation=config.head_act)
        fused_head = backend.fused_head or (config.head_placement == "fused"
                                            and backend.mode == "fused")
        vmem_head = head_plan if fused_head else None
        max_safe = plan.max_safe_batch(head=vmem_head, tile_h=tile_h)
        # a streamed chunk's input block is double-buffered
        chunk_safe = plan.max_safe_batch(head=vmem_head, tile_h=tile_h,
                                         streamed=True)
        # The VMEM residency model describes the FUSED kernel (whole-batch
        # input resident on-chip); per-pass/grouped kernels stream row
        # blocks and are batch-size-indifferent.  interpret=None resolves
        # through the kernel layer's own switch, so a default manifest
        # built on the chip (compiled) is checked at build time.
        compiled = not resolve_interpret(config.interpret)
        stream_chunk: Optional[int] = None
        if backend.mode == "fused":
            streams = backend.streamed or config.max_batch > max_safe
            if compiled and (max_safe < 1 or (streams and chunk_safe < 1)):
                raise cls._unlaunchable(config, plan, vmem_head, tile_h,
                                        streamed=max_safe >= 1)
            if backend.streamed:
                chunk = tuning.micro_batch if tuning is not None else 0
                if compiled:
                    chunk = (min(chunk, chunk_safe) if chunk >= 1
                             else chunk_safe)
                elif chunk < 1:
                    chunk = chunk_safe if chunk_safe >= 1 \
                        else config.max_batch
                stream_chunk = max(1, min(chunk, config.max_batch))
            elif compiled and streams:
                # Over-budget micro-batch on the plain fused path: pipeline
                # it instead of rejecting the deployment.
                stream_chunk = chunk_safe
                log.append(cls._pipelining_note(config, max_safe, tile_h,
                                                stream_chunk))
        codec = get_codec(config.codec)
        mode, interpret = backend.mode, config.interpret
        head_act = config.head_act

        def edge_apply(edge_params, obs):
            # deployment path: the prebuilt plan is reused (and
            # size-checked) on every frame
            return miniconv_apply(edge_params, spec, obs, use_kernel=mode,
                                  plan=plan if mode == "fused" else None,
                                  tile_h=tile_h, interpret=interpret,
                                  stream_chunk=stream_chunk)

        def server_apply(server_params, feats):
            with scope("split.project"):
                z = dense(server_params["proj"],
                          feats.reshape(feats.shape[0], -1))
                return _ACTS[head_act](z)

        split = SplitModel(edge_apply=edge_apply, server_apply=server_apply,
                           codec=codec,
                           quantize_in_train=config.quantize_in_train,
                           plan=plan)

        def init(key):
            return miniconv_encoder_init(key, spec, h=config.in_h,
                                         w=config.in_w,
                                         feature_dim=config.head_dim)

        if config.head_placement == "fused" or backend.fused_head:
            def encoder_apply(params, obs):
                # encoder + projection in one call (one kernel launch
                # under the fused backends)
                p = plan if (mode == "fused"
                             and obs.shape[1:3] == (plan.in_h, plan.in_w)) \
                    else None
                with scope("miniconv.encode"):
                    _, z = miniconv_apply(
                        params["edge"], spec, obs, use_kernel=mode, plan=p,
                        tile_h=tile_h, head=params["server"]["proj"],
                        head_act=head_act, interpret=interpret,
                        stream_chunk=stream_chunk if p is not None else None)
                return z
        else:
            def encoder_apply(params, obs):
                # training path tolerates other input sizes: re-lower the
                # plan when the observation differs from the deployed size
                p = plan if (mode == "fused"
                             and obs.shape[1:3] == (plan.in_h, plan.in_w)) \
                    else None
                with scope("miniconv.encode"):
                    feats = miniconv_apply(
                        params["edge"], spec, obs, use_kernel=mode, plan=p,
                        tile_h=tile_h, interpret=interpret,
                        stream_chunk=stream_chunk if p is not None else None)
                    return server_apply(params["server"], feats)

        encoder = Encoder(name=f"miniconv{spec.k_out}", init=init,
                          apply=encoder_apply, spec=spec)
        return cls(config=config, backend=backend, plan=plan,
                   head_plan=head_plan, codec=codec, split=split,
                   encoder=encoder, max_safe_batch=max_safe, tile_h=tile_h,
                   stream_chunk=stream_chunk, compiled=compiled,
                   build_log=tuple(log))

    @classmethod
    def _build_impala(cls, config: DeploymentConfig) -> "Deployment":
        """The server-only placement of an IMPALA-CNN: the edge half is the
        identity on the frame, the codec carries the frame, and the server
        half (and ``encoder.apply``) is torso plus head on ``xla``."""
        spec, act = config.spec, _ACTS[config.head_act]

        def edge_apply(edge_params, obs):
            return obs

        def server_apply(server_params, frames):
            # a stacked micro-batch of one-frame payloads is (B, 1, H, W, C)
            frames = frames.reshape((-1,) + frames.shape[-3:])
            return impala.apply(spec, server_params, frames, act)

        def init(key):
            return {"edge": {},
                    "server": impala.init(key, spec, h=config.in_h,
                                          w=config.in_w,
                                          head_dim=config.head_dim)}

        def encoder_apply(params, obs):
            return server_apply(params["server"], obs)

        split = SplitModel(edge_apply=edge_apply, server_apply=server_apply,
                           codec=get_codec(config.codec),
                           quantize_in_train=config.quantize_in_train)
        return cls(config=config, backend=get_backend(config.backend),
                   plan=None, head_plan=None, codec=split.codec, split=split,
                   encoder=Encoder(name="impala", init=init,
                                   apply=encoder_apply),
                   max_safe_batch=config.max_batch, tile_h=config.tile_h)

    # ---- over-budget diagnostics ------------------------------------------
    @staticmethod
    def _suggestion(config) -> str:
        """The tuner's cost-model pick, formatted for diagnostics."""
        from repro.core.tuning import suggest_tuning
        try:
            s = suggest_tuning(config)
        except ValueError:
            return ""
        return (f"; tuner suggests backend={s.backend} tile_h={s.tile_h} "
                f"micro_batch={s.micro_batch} (python -m repro.deploy "
                f"--tune to measure and freeze)")

    @classmethod
    def _unlaunchable(cls, config, plan, vmem_head, tile_h,
                      streamed=False) -> ValueError:
        need = plan.vmem_bytes(1, head=vmem_head, tile_h=tile_h,
                               streamed=streamed)
        from repro.core.passplan import DEFAULT_VMEM_LIMIT
        return ValueError(
            f"compiled fused launch cannot fit VMEM at ANY batch size: one "
            f"{plan.in_h}x{plan.in_w} frame"
            f"{' (double-buffered, streamed)' if streamed else ''} needs "
            f"~{need / 2**20:.2f} MiB "
            f"> budget {DEFAULT_VMEM_LIMIT / 2**20:.2f} MiB "
            f"(max_safe_batch=0, tile_h={tile_h}) — batch pipelining "
            f"cannot help; lower the input size or split the spec"
            + cls._suggestion(config))

    @classmethod
    def _pipelining_note(cls, config, max_safe, tile_h, chunk) -> str:
        return (f"pipelining: max_batch {config.max_batch} exceeds "
                f"max_safe_batch {max_safe} (tile_h={tile_h}) — streaming "
                f"the fused launch in {chunk}-frame chunks "
                f"(kernels.miniconv_pass.miniconv_encoder_stream)"
                + cls._suggestion(config))

    # ---- parameters --------------------------------------------------------
    def init(self, key):
        """{"edge": conv params, "server": {"proj": dense}} — the dict split
        IS the deployment split.  The impala family's edge is empty: its
        torso and head are all ``"server"``."""
        return self.encoder.init(key)

    # ---- accounting --------------------------------------------------------
    @property
    def spec(self) -> MiniConvSpec | ImpalaSpec:
        """The encoder's spec, of either family (``config.family``)."""
        return self.config.spec

    @property
    def wire_bytes(self) -> int:
        """Exact bytes of one request's payload on the link."""
        return self.wire_bytes_batch(1)

    def wire_bytes_batch(self, batch: Optional[int] = None) -> int:
        """Exact link bytes of ``batch`` requests (default ``max_batch``),
        each with its codec header: MiniConv sends its feature map, the
        server-only impala family its frame."""
        b = self.config.max_batch if batch is None else batch
        if self.plan is None:
            c = self.config
            return self.split.wire_bytes((c.in_h, c.in_w, c.spec.c_in),
                                         batch=b)
        return self.split.wire_bytes(batch=b)

    @property
    def frame_bytes(self) -> int:
        """Bytes of the raw observation upload of a server-only deployment:
        the MiniConv baseline's RGBA-packed frame (4 channels per texture),
        or the impala family's ``in_h·in_w·c_in`` frame as it is."""
        c = self.spec.c_in
        if self.config.family == "miniconv":
            c = -(-c // 4) * 4
        return self.config.in_h * self.config.in_w * c

    # ---- served pipeline ---------------------------------------------------
    @staticmethod
    def _split_params(params):
        """Accept either the encoder split ({"edge", "server"}) or a full
        TRAINED parameter pytree (``TrainResult.params`` /
        ``TrainState.params``, whose ``"encoder"`` entry is that split) —
        so a training run serves from the manifest with no repacking."""
        if "edge" not in params and "encoder" in params:
            return params["encoder"]
        return params

    def edge_fn(self, params) -> Callable:
        """Jitted on-device half: obs -> wire payload."""
        edge_params = self._split_params(params)["edge"]
        return jax.jit(lambda obs: self.split.edge_step(edge_params, obs))

    def server_fn(self, params, head: Optional[Callable] = None) -> Callable:
        """Jitted remote half: payload -> features (or actions via
        ``head``, e.g. a policy MLP applied after the projection)."""
        server_params = self._split_params(params)["server"]

        def fn(payload):
            z = self.split.server_step(server_params, payload)
            return head(z) if head is not None else z
        return jax.jit(fn)

    def server_batch_fn(self, params,
                        head: Optional[Callable] = None) -> Callable:
        """Jitted micro-batched remote half: stacked payload -> actions."""
        server_params = self._split_params(params)["server"]

        def fn(payload_batch):
            z = self.split.server_step_batch(server_params, payload_batch)
            return head(z) if head is not None else z
        return jax.jit(fn)

    def client(self, params) -> EdgeClient:
        """Ready :class:`EdgeClient` for these parameters."""
        return EdgeClient(encode_fn=self.edge_fn(params),
                          wire_bytes=self.wire_bytes)

    def server(self, params,
               head: Optional[Callable] = None) -> BatchingPolicyServer:
        """Ready :class:`BatchingPolicyServer` under this config's
        batching policy (``max_batch`` / ``max_wait_ms``)."""
        return BatchingPolicyServer(
            serve_batch_fn=self.server_batch_fn(params, head),
            max_batch=self.config.max_batch,
            max_wait_s=self.config.max_wait_ms / 1e3)

    def serving_pair(self, params, head: Optional[Callable] = None
                     ) -> tuple[EdgeClient, BatchingPolicyServer]:
        """The paper's Figure-5 pipeline, ready to measure."""
        return self.client(params), self.server(params, head)

    def export_best(self, population, head: Optional[Callable] = None
                    ) -> tuple[EdgeClient, BatchingPolicyServer]:
        """Serving pair for a population run's winning member.

        ``population`` is a :class:`repro.rl.population.PopulationResult`;
        the winner is its ``best_member()`` — highest ``final_100_mean``
        under the deterministic eval protocol.  The member's trained
        params serve through THIS manifest exactly like the single-run
        path (:meth:`serving_pair` accepts ``TrainState.params``
        directly), so train-many / freeze-best / serve-on-fleet is one
        manifest round-trip.
        """
        return self.serving_pair(population.best_params(), head=head)

    def fleet_sim(self, service_model: Callable[[int], float], *, uplink,
                  rate_hz: float = 10.0, horizon_s: float = 5.0,
                  action_bytes: int = 64,
                  n_servers: Optional[int] = None,
                  router: Optional[str] = None,
                  max_batch: Optional[int] = None,
                  max_wait_s: Optional[float] = None) -> FleetQueueSim:
        """Fleet-scale queue simulator for THIS deployment.

        Payload bytes, micro-batching policy and fleet shape
        (``n_servers`` / ``router``) all come from the manifest —
        keyword overrides take precedence, so a benchmark sweeping the
        batching policy can keep the sim consistent with the policy it
        MEASURED t(B) under; ``service_model`` is that measured curve
        (``BatchingPolicyServer.service_model()``), charged by every
        server in the fleet.  At ``n_servers=1`` this is exactly the
        Table 6 batched simulation.
        """
        cfg = self.config
        return FleetQueueSim(
            service_time_s=service_model(1), uplink=uplink,
            payload_bytes=self.wire_bytes, action_bytes=action_bytes,
            rate_hz=rate_hz, horizon_s=horizon_s,
            max_batch=cfg.max_batch if max_batch is None else max_batch,
            max_wait_s=cfg.max_wait_ms / 1e3 if max_wait_s is None
            else max_wait_s,
            service_model=service_model,
            n_servers=cfg.n_servers if n_servers is None else n_servers,
            router=cfg.router if router is None else router)

    def scenario_sim(self, scenario, *,
                     n_servers: Optional[int] = None,
                     router: Optional[str] = None,
                     max_batch: Optional[int] = None,
                     max_wait_s: Optional[float] = None,
                     adaptation: str = "none",
                     service_model: Optional[Callable[[int], float]] = None):
        """This deployment under a named (or inline) :class:`Scenario`.

        The scenario supplies the serving CONDITION — its seeded link,
        its device zoo (one t(B) curve per server, cycled from the
        profile registry), client population/rate and adaptation-mode
        ladder — while the manifest supplies the deployment: payload
        bytes (``wire_bytes``), micro-batching policy and fleet shape,
        with the same keyword-override precedence as :meth:`fleet_sim`.
        ``adaptation`` picks the controller (``"none"``, ``"rule"``,
        ``"static:<i>"`` or anything registered via
        ``repro.serving.scenario.register_adaptation``); a measured
        ``service_model`` overrides the zoo on every server.  Returns a
        :class:`~repro.serving.scenario.ScenarioFleetSim` — call
        ``.report(n_clients)`` for latencies, uplink bytes and the
        delivered-return proxy.
        """
        from repro.serving.scenario import get_scenario
        sc = get_scenario(scenario)
        cfg = self.config
        ns = cfg.n_servers if n_servers is None else n_servers
        return sc.sim(
            self.wire_bytes, n_servers=ns,
            router=cfg.router if router is None else router,
            max_batch=cfg.max_batch if max_batch is None else max_batch,
            max_wait_s=cfg.max_wait_ms / 1e3 if max_wait_s is None
            else max_wait_s,
            adaptation=adaptation,
            service_models=None if service_model is None
            else (service_model,) * ns)

    def fleet(self, params, *, n_servers: Optional[int] = None,
              router: Optional[str] = None, max_batch: Optional[int] = None,
              service_model: Optional[Callable[[int], float]] = None,
              timeout_s: float = 10.0, retries: int = 2,
              precompile: bool = True, start: bool = True,
              shaping=None):
        """A REAL fleet for THIS deployment (localhost).

        The counterpart of :meth:`fleet_sim`: ``n_servers`` workers (each
        rebuilding the jitted server half from this manifest),
        length-prefix-framed sockets carrying the wire codec's payloads,
        and the registered routing policy at the front door
        (``repro.serving.realfleet``).  On the CPU the workers are
        spawned processes; on an accelerator they run in this process,
        replica ``i`` on ``jax.devices()[i]``.  Fleet shape defaults to
        the manifest (``n_servers`` / ``router`` / ``max_batch``), exactly
        like the simulator.

        When a measured ``service_model`` is given, worker admission is
        capped at its :attr:`~repro.serving.server.BatchServiceModel.
        max_measured_batch` — the real fleet never serves batch sizes the
        t(B) curve only extrapolates, so the sim-vs-real calibration
        compares measured numbers on both sides.

        ``shaping`` (a :class:`~repro.serving.realfleet.ShapingConfig`
        or its dict) token-bucket-shapes every worker's request ingress —
        the measured counterpart of the sims' shaped uplink.

        Returns a started :class:`~repro.serving.realfleet.RealFleet`
        (``start=False`` defers the start); always ``close()`` it — the
        returned leak list is the CI "no leaked workers" gate.
        """
        import numpy as np
        from repro.serving.realfleet import RealFleet
        cfg = self.config
        cap = cfg.max_batch if max_batch is None else max_batch
        if service_model is not None and hasattr(service_model,
                                                 "max_measured_batch"):
            cap = min(cap, service_model.max_measured_batch)
        params_np = jax.tree.map(np.asarray, self._split_params(params))
        fl = RealFleet(
            cfg.to_dict(), params_np,
            n_servers=cfg.n_servers if n_servers is None else n_servers,
            router=cfg.router if router is None else router,
            max_batch=max(1, cap), timeout_s=timeout_s, retries=retries,
            precompile=precompile, shaping=shaping)
        return fl.start() if start else fl


# ---------------------------------------------------------------------------
# Manifest CLI: python -m repro.deploy
# ---------------------------------------------------------------------------

def _verify_roundtrip(cfg: DeploymentConfig, *, seed: int = 0) -> None:
    """Assert a reloaded manifest reproduces identical encoder outputs and
    wire payloads (the ISSUE-3 acceptance criterion)."""
    import numpy as np
    cfg2 = DeploymentConfig.from_json(cfg.to_json())
    assert cfg2 == cfg, "manifest round-trip changed the config"
    dep, dep2 = Deployment.build(cfg), Deployment.build(cfg2)
    key = jax.random.PRNGKey(seed)
    params = dep.init(key)
    params2 = dep2.init(key)
    obs = jax.random.uniform(jax.random.PRNGKey(seed + 1),
                             (1, cfg.in_h, cfg.in_w, cfg.spec.c_in))
    np.testing.assert_array_equal(dep.encoder.apply(params, obs),
                                  dep2.encoder.apply(params2, obs))
    p1 = dep.split.edge_step(params["edge"], obs)
    p2 = dep2.split.edge_step(params2["edge"], obs)
    for k in p1:
        np.testing.assert_array_equal(p1[k], p2[k])


def _real_fleet_check(cfg: DeploymentConfig, *, n_requests: int = 8,
                      seed: int = 0) -> None:
    """Launch the manifest's real multi-process fleet on localhost, serve
    ``n_requests`` over sockets, and assert the actions are bitwise-equal
    to in-process serving — then shut down and assert no worker leaked."""
    import numpy as np
    dep = Deployment.build(cfg)
    params = dep.init(jax.random.PRNGKey(seed))
    client, server = dep.serving_pair(params)
    obs = jax.random.uniform(
        jax.random.PRNGKey(seed + 1),
        (n_requests, cfg.in_h, cfg.in_w, cfg.spec.c_in))
    payloads = [client.encode_fn(obs[i:i + 1]) for i in range(n_requests)]
    want = [np.asarray(server.serve([p])[0]) for p in payloads]
    fleet = dep.fleet(params)
    try:
        got = [fleet.request(p, client=i) for i, p in enumerate(payloads)]
        per_server = list(fleet.stats["per_server"])
    finally:
        leaked = fleet.close()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert not leaked, f"leaked worker processes: {leaked}"
    print(f"  real fleet: {cfg.n_servers} worker(s) via {cfg.router} served "
          f"{n_requests} requests over sockets (per-server {per_server}); "
          f"actions bitwise-equal to in-process serving; clean shutdown, "
          f"no leaked workers")


def _scenario_report(dep: "Deployment", name: str) -> None:
    """Run one registered scenario against this deployment and print the
    static-vs-adaptive scorecard (sim only — no processes spawned)."""
    from repro.serving.scenario import get_scenario
    sc = get_scenario(name)
    print(f"  scenario {sc.name}: link={sc.link_kind} seed={sc.seed} "
          f"devices={','.join(sc.devices)} N={sc.n_clients} "
          f"rate={sc.rate_hz}Hz horizon={sc.horizon_s}s "
          f"deadline={sc.deadline_s * 1e3:.0f}ms")
    policies = ([f"static:{i}" for i in range(len(sc.modes))]
                + (["rule"] if len(sc.modes) > 1 else []))
    for adapt in policies:
        rep = dep.scenario_sim(sc, adaptation=adapt).report(sc.n_clients)
        modes = " ".join(f"{k}={v}" for k, v in rep.mode_counts().items()
                         if v)
        print(f"    {adapt:<9} p95={rep.p95_s * 1e3:8.2f}ms "
              f"mean={rep.mean_s * 1e3:7.2f}ms "
              f"return={rep.delivered_return:.4f} "
              f"bytes={rep.total_uplink_bytes / 1e6:.3f}MB  [{modes}]")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Build the standard deployment config, write its "
                    "manifest, reload it and verify the round-trip.")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--c-in", type=int, default=12)
    ap.add_argument("--x", type=int, default=84, help="input H=W")
    ap.add_argument("--backend", default="fused",
                    help=f"one of: {', '.join(backend_names())}")
    ap.add_argument("--codec", default="uint8")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--n-servers", type=int, default=1,
                    help="fleet size for the sharded serving simulation")
    ap.add_argument("--router", default="round_robin",
                    help=f"fleet routing policy: {', '.join(ROUTERS)}")
    ap.add_argument("--out", default="deploy_manifest.json")
    ap.add_argument("--verify", action="store_true",
                    help="rebuild from the reloaded manifest and assert "
                         "identical encoder outputs and wire payloads")
    ap.add_argument("--tune", action="store_true",
                    help="autotune backend/tile_h/micro-batch for this "
                         "config (core.tuning) and freeze the winning "
                         "TunedPlan into the written manifest")
    ap.add_argument("--tune-iters", type=int, default=5,
                    help="timing repetitions per measured candidate")
    ap.add_argument("--real-fleet", action="store_true",
                    help="launch the manifest's REAL multi-process fleet "
                         "on localhost (n_servers worker processes behind "
                         "the configured router), verify socket-served "
                         "actions are bitwise-equal to in-process serving, "
                         "and shut down cleanly")
    ap.add_argument("--fleet-requests", type=int, default=8,
                    help="requests served during the --real-fleet check")
    ap.add_argument("--scenario", default=None,
                    help="run the manifest through a registered serving "
                         "scenario (repro.serving.scenario: seeded "
                         "adversarial link + device zoo) and print the "
                         "no-adaptation / per-static-mode / rule-"
                         "controller comparison")
    args = ap.parse_args(argv)

    cfg = DeploymentConfig.standard(k=args.k, c_in=args.c_in, h=args.x,
                                    backend=args.backend, codec=args.codec,
                                    max_batch=args.max_batch,
                                    n_servers=args.n_servers,
                                    router=args.router)
    if args.tune:
        from repro.core.tuning import tune
        print(f"  tuning {args.backend} X={args.x} "
              f"max_batch={args.max_batch} ...")
        tp = tune(cfg, iters=args.tune_iters, log=print)
        cfg = dataclasses.replace(cfg, tuning=tp)
        print(f"  tuned: backend={tp.backend} tile_h={tp.tile_h} "
              f"micro_batch={tp.micro_batch} "
              f"({tp.per_frame_s * 1e6:.1f} us/frame, mode={tp.mode}, "
              f"searched={tp.searched} pruned={tp.pruned})")
    dep = Deployment.build(cfg)
    for line in dep.build_log:
        print(f"  {line}")
    with open(args.out, "w") as f:
        f.write(cfg.to_json(indent=2))
    print(f"  wrote {args.out}")
    reloaded = DeploymentConfig.from_json(open(args.out).read())
    assert reloaded == cfg, "manifest on disk does not round-trip"
    print(f"  round-trip OK: backend={dep.backend.name} "
          f"plan={dep.plan.total_passes} passes "
          f"feature={dep.plan.feature_shape} wire={dep.wire_bytes}B "
          f"max_safe_batch={dep.max_safe_batch} "
          f"fleet={cfg.n_servers}x/{cfg.router}")
    if args.verify:
        _verify_roundtrip(cfg)
        print("  verified: reloaded manifest reproduces identical encoder "
              "outputs and wire payloads")
    if args.real_fleet:
        _real_fleet_check(reloaded, n_requests=args.fleet_requests)
    if args.scenario:
        _scenario_report(dep, args.scenario)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()


__all__ = ["CONFIG_VERSION", "Deployment", "DeploymentConfig"]
