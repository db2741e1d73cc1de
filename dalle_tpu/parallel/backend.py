"""Pluggable distributed backend — the reference's `DistributedBackend` contract,
re-grounded on JAX collectives.

The reference (dalle_pytorch/distributed_backends/distributed_backend.py:12-178)
defines an ABC with eight overridables plus a registry/CLI layer
(distributed_utils.py:22-76). Transports were DeepSpeed→NCCL and Horovod→MPI, with a
`DummyBackend` no-op for single-process runs. Here the same surface is implemented
on `jax.distributed` + device meshes:

  * ``initialize`` → ``jax.distributed.initialize()`` (multi-host) + mesh build over
    ICI/DCN, instead of NCCL process groups.
  * ``average_all`` → on-host ``jax.pmean``-style mean via ``jax.device_get`` of an
    already-replicated scalar, or psum inside the jitted step (the idiomatic place —
    see parallel/partition.py; gradient averaging never happens post-hoc here).
  * ``local_barrier`` → ``multihost_utils.sync_global_devices``.
  * ``distribute`` → returns a sharded train-step + sharded params rather than a
    wrapped module (JAX has no mutable module to wrap).

`DummyBackend` parity = a 1-device mesh.
"""

from __future__ import annotations

import argparse
import os
from abc import ABC, abstractmethod
from typing import Any, Optional

import jax
import numpy as np

from ..chaos import io_hook
from ..config import MeshConfig
from ..utils.retry import TRANSIENT, with_retry
from .mesh import build_mesh, single_device_mesh


class DistributedBackend(ABC):
    """Same eight-method contract as the reference ABC
    (distributed_backends/distributed_backend.py:12-28)."""

    BACKEND_MODULE_NAME: str = "jax"
    BACKEND_NAME: str = "Base"

    ROOT_RANK = 0

    def __init__(self):
        self.mesh = None
        self._initialized = False

    # -- lifecycle ---------------------------------------------------------
    def has_backend(self) -> bool:
        return True

    def initialize(self, mesh_config: Optional[MeshConfig] = None):
        self._backend_initialize(mesh_config or MeshConfig())
        self._initialized = True
        return self

    def require_init(self):
        assert self._initialized, f"{self.BACKEND_NAME} backend used before initialize()"

    # -- abstract surface --------------------------------------------------
    @abstractmethod
    def wrap_arg_parser(self, parser: argparse.ArgumentParser) -> argparse.ArgumentParser: ...

    @abstractmethod
    def _backend_initialize(self, mesh_config: MeshConfig): ...

    @abstractmethod
    def _get_world_size(self) -> int: ...

    @abstractmethod
    def _get_rank(self) -> int: ...

    @abstractmethod
    def _get_local_rank(self) -> int: ...

    @abstractmethod
    def _local_barrier(self): ...

    @abstractmethod
    def _distribute(self, *, params=None, optimizer_state=None, train_step=None, **kw): ...

    @abstractmethod
    def _average_all(self, value): ...

    # -- public wrappers (mirror reference names) -------------------------
    def get_world_size(self) -> int:
        self.require_init()
        return self._get_world_size()

    def get_rank(self) -> int:
        self.require_init()
        return self._get_rank()

    def get_local_rank(self) -> int:
        self.require_init()
        return self._get_local_rank()

    def is_root_worker(self) -> bool:
        return self.get_rank() == self.ROOT_RANK

    def is_local_root_worker(self) -> bool:
        return self.get_local_rank() == self.ROOT_RANK

    def local_barrier(self):
        self.require_init()
        self._local_barrier()

    def distribute(self, **kw):
        self.require_init()
        return self._distribute(**kw)

    def average_all(self, value):
        self.require_init()
        return self._average_all(value)

    def check_batch_size(self, batch_size: int):
        # reference: batch must be >= world size (distributed_backend.py:56-60)
        assert batch_size >= self.get_world_size(), (
            f"batch size {batch_size} < world size {self.get_world_size()}")


class JaxBackend(DistributedBackend):
    """The TPU backend: one process per host, a global mesh over all chips."""

    BACKEND_NAME = "jax"

    # coordinator-connect retry policy (utils/retry.py); class-level so the
    # elastic runtime / tests can widen or pin it fleet-wide
    connect_retry_kw = {"attempts": 5, "base_delay_s": 0.2,
                        "max_delay_s": 2.0}

    def wrap_arg_parser(self, parser):
        grp = parser.add_argument_group("jax distributed backend")
        grp.add_argument("--coordinator_address", type=str, default=None,
                         help="host:port of process 0 (multi-host only)")
        grp.add_argument("--num_processes", type=int, default=None)
        grp.add_argument("--process_id", type=int, default=None)
        return parser

    def __init__(self):
        super().__init__()
        self._coordinator_address = None
        self._num_processes = None
        self._process_id = None

    def configure_from_args(self, args):
        """Stash multi-host flags parsed by wrap_arg_parser (CLI wins over env)."""
        self._coordinator_address = getattr(args, "coordinator_address", None)
        self._num_processes = getattr(args, "num_processes", None)
        self._process_id = getattr(args, "process_id", None)
        return self

    def _backend_initialize(self, mesh_config: MeshConfig):
        coord = self._coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
        nproc = self._num_processes or os.environ.get("JAX_NUM_PROCESSES")
        if coord and nproc and int(nproc) > 1:
            pid = self._process_id
            if pid is None:
                env_pid = os.environ.get("JAX_PROCESS_ID")
                pid = int(env_pid) if env_pid is not None else None
            # CPU fleets (the DCN tests / local multi-process dev) run their
            # cross-process collectives over gloo, jax's default
            # jax_cpu_collectives_implementation — nothing to configure.
            # pid None → jax.distributed.initialize infers it from platform
            # metadata (the TPU-pod norm); forcing 0 would collide across
            # hosts. The connect is retried with jittered backoff
            # (utils/retry.py): worker N dialing in before the coordinator
            # listens — routine during elastic reconfiguration, when every
            # survivor restarts at once — used to be a single attempt and a
            # dead worker. XlaRuntimeError (DEADLINE_EXCEEDED and friends)
            # is a RuntimeError, hence the widened retry_on; a genuinely
            # unreachable coordinator still fails after the budget, which
            # the elastic agent treats as a failed epoch.
            def _connect():
                io_hook("coordinator_connect")   # chaos injection point
                try:
                    jax.distributed.initialize(
                        coordinator_address=coord,
                        num_processes=int(nproc),
                        process_id=pid,
                    )
                except Exception:  # noqa: BLE001 - any failed dial must
                    # reset the process-global distributed state before
                    # re-raising: jax assigns the client BEFORE connecting,
                    # so without the shutdown every later attempt would die
                    # on "initialize should only be called once" instead of
                    # actually redialing
                    try:
                        jax.distributed.shutdown()
                    except Exception:  # noqa: BLE001 - nothing was
                        pass           # initialized; keep the real error
                    raise

            with_retry("coordinator_connect", _connect,
                       retry_kw=dict(self.connect_retry_kw,
                                     retry_on=TRANSIENT + (RuntimeError,)))
        self.mesh = build_mesh(mesh_config)

    def _get_world_size(self) -> int:
        return jax.device_count()

    def _get_rank(self) -> int:
        # global rank of this host's first worker slot = number of devices on
        # lower-indexed processes (correct even when hosts own unequal device
        # counts, unlike process_index * local_device_count)
        me = jax.process_index()
        return sum(1 for d in jax.devices() if d.process_index < me)

    def _get_local_rank(self) -> int:
        return 0  # one process per host; local root == this process

    def is_root_worker(self) -> bool:
        return jax.process_index() == 0

    def is_local_root_worker(self) -> bool:
        return True

    def _local_barrier(self):
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("dalle_tpu_barrier")
        # single host: nothing to synchronize

    def _distribute(self, *, params=None, optimizer_state=None, train_step=None,
                    partition_rules=None, **kw):
        """Shard params/opt-state onto the mesh and return (params, opt_state, step).

        Unlike DeepSpeed's engine wrapper (deepspeed_backend.py:135-163), the
        gradient allreduce lives *inside* the jitted step as a psum induced by
        sharding annotations; nothing is wrapped.
        """
        from .partition import shard_params
        out = []
        if params is not None:
            params = shard_params(self.mesh, params, partition_rules)
            out.append(params)
        if optimizer_state is not None:
            optimizer_state = shard_params(self.mesh, optimizer_state, partition_rules)
            out.append(optimizer_state)
        if train_step is not None:
            out.append(train_step)
        return tuple(out) if len(out) != 1 else out[0]

    def _average_all(self, value):
        """Mean over data-parallel replicas. For values produced by the jitted step
        this is already a global mean (psum in-graph); host-side scalars in a
        multi-host run go through process_allgather."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            arr = multihost_utils.process_allgather(np.asarray(value))
            return np.mean(arr)
        return np.asarray(jax.device_get(value)).mean()


class DummyBackend(DistributedBackend):
    """1-device no-op backend — parity with the reference's DummyBackend
    (distributed_backends/dummy_backend.py): lets every 'distributed' script run
    single-process with no cluster."""

    BACKEND_NAME = "Dummy"

    def wrap_arg_parser(self, parser):
        return parser

    def _backend_initialize(self, mesh_config: MeshConfig):
        self.mesh = single_device_mesh()

    def _get_world_size(self): return 1
    def _get_rank(self): return self.ROOT_RANK
    def _get_local_rank(self): return self.ROOT_RANK
    def _local_barrier(self): pass

    def _distribute(self, *, params=None, optimizer_state=None, train_step=None, **kw):
        out = [x for x in (params, optimizer_state, train_step) if x is not None]
        return tuple(out) if len(out) != 1 else out[0]

    def _average_all(self, value):
        return np.asarray(jax.device_get(value)).mean()


# --------------------------------------------------------------------------
# Registry + CLI selection (reference: distributed_utils.py:22-96)
# --------------------------------------------------------------------------

BACKENDS = {
    JaxBackend.BACKEND_NAME.lower(): JaxBackend,
    DummyBackend.BACKEND_NAME.lower(): DummyBackend,
    # reference CLI names (distributed_utils.py:22-26): the GPU engines don't
    # exist on TPU — both map onto the jax mesh backend, which covers their
    # used surface (allreduce/barrier/rank queries/distribute)
    "deepspeed": JaxBackend,
    "horovod": JaxBackend,
}

is_distributed: Optional[bool] = None
backend: Optional[DistributedBackend] = None


def wrap_arg_parser(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument(
        "--distributed_backend", "--distr_backend", type=str, default=None,
        help=f"which distributed backend to use: {list(BACKENDS)}")
    # aliases map several names onto one class — add each class's flags once
    for cls in dict.fromkeys(BACKENDS.values()):
        cls().wrap_arg_parser(parser)
    return parser


def set_backend_from_args(args) -> DistributedBackend:
    """Select & validate the backend from parsed args (ref distributed_utils.py:48-76)."""
    global is_distributed, backend
    name = (getattr(args, "distributed_backend", None) or "dummy").lower()
    if name not in BACKENDS:
        raise ValueError(f"unknown distributed backend {name!r}; options: {list(BACKENDS)}")
    if (BACKENDS[name] is JaxBackend
            and name != JaxBackend.BACKEND_NAME.lower()):
        print(f"[distributed] backend {name!r} is a GPU engine; using the "
              f"TPU-native jax mesh backend (same collective surface)")
    backend = BACKENDS[name]()
    if not backend.has_backend():
        raise ModuleNotFoundError(f"backend {name} is not available")
    if hasattr(backend, "configure_from_args"):
        backend.configure_from_args(args)
    is_distributed = name != "dummy"
    return backend


def using_backend(test_backend) -> bool:
    """Type-or-name check (ref distributed_utils.py:87-96)."""
    assert backend is not None, "select a backend first"
    if isinstance(test_backend, str):
        return backend.BACKEND_NAME.lower() == test_backend.lower()
    return isinstance(backend, test_backend)
