"""JaxConfig/JaxBackend: the TPU-native replacement for the reference's
torch NCCL process-group setup (train/torch/config.py:54
_setup_torch_process_group).

Instead of NCCL rendezvous, the gang wires the jax coordination service:
rank 0 publishes coordinator host:port, every rank calls
jax.distributed.initialize(coordinator, num_processes, process_id); XLA
then runs collectives over ICI within a slice and DCN across hosts.  Each
worker builds the gang's device Mesh from ScalingConfig's parallelism
axes; the user loop reads it via session.get_mesh().
"""

from __future__ import annotations

import dataclasses

import ray_tpu
from ray_tpu.train.backend import Backend, BackendConfig


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _init_jax_distributed(coordinator: str, num_processes: int,
                          process_id: int):
    import jax

    from ray_tpu._private.jax_utils import enable_cpu_collectives
    enable_cpu_collectives()
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def _coordinator_host() -> str:
    import socket
    return socket.gethostbyname(socket.gethostname())


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    """use_distributed: wire jax.distributed across the gang (multi-host
    pods).  With one worker (single host owning the whole slice/chip) the
    coordination service is unnecessary and skipped."""
    use_distributed: bool = True
    virtual_cpu_devices: int = 0  # >0: force a virtual CPU mesh (tests)

    @property
    def backend_cls(self):
        return JaxBackend


class JaxBackend(Backend):
    def __init__(self):
        self._scaling_config = None
        self._config = None

    def on_start(self, worker_group, backend_config: JaxConfig):
        self._config = backend_config
        # JaxTrainer.training_loop stashes the ScalingConfig here so the
        # per-worker mesh builder knows the parallelism axes.
        self._scaling_config = getattr(backend_config, "_scaling_config",
                                       None)
        n = worker_group.num_workers
        if backend_config.use_distributed and n > 1:
            host = worker_group.execute_single(0, _coordinator_host)
            port = worker_group.execute_single(0, _free_port)
            coordinator = f"{host}:{port}"
            refs = [
                w.execute.remote(_init_jax_distributed, coordinator, n, i)
                for i, w in enumerate(worker_group.workers)
            ]
            ray_tpu.get(refs, timeout=300)

    def mesh_builder(self):
        """Returns a callable run ON each worker to build the gang mesh."""
        sc = self._scaling_config
        cfg = self._config
        virtual = cfg.virtual_cpu_devices if cfg else 0

        def _build():
            from ray_tpu._private.jax_utils import (cpu_mesh_devices,
                                                    open_backend)
            from ray_tpu.parallel.mesh import make_mesh
            import jax
            if virtual:
                devices = cpu_mesh_devices(virtual)
            else:
                open_backend()   # a TPU worker's jax.backend_init span
                devices = jax.devices()
            if sc is None:
                return None
            spec = sc.mesh_spec(len(devices))
            return make_mesh(spec, devices=devices)

        return _build
