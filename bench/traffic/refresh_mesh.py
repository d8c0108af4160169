"""Traffic driver ``refresh_mesh``: the ``refresh`` traffic over a
federation whose session shards its client axis over the chips of one
host.

The session is the normal one, ``AggregationSession(..., mesh=)``: its
sketch and parameter buffers are sharded by client row over a ``Mesh``
of the cell's ``chips`` devices on the configuration's
``session.mesh_axis``; each upload wave is copied to every chip and each
chip writes its own rows; a round's clustering runs on the mesh's first
chip and its labels and centres are copied back to every chip for the
sharded mean.  Everything else (cycles, waves, rounds, output) is the
``refresh`` driver's.

Mix keys: those of ``refresh``."""
from __future__ import annotations

import os

from bench import generator

refresh = generator.driver(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "refresh")


class MeshFederation(generator.Federation):
    """A ``Federation`` whose session and devices are a mesh of the
    configuration's ``chips``."""

    def __init__(self, config: dict, seed: int, mix: dict):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.core.engine import AggregationSession
        from repro.serving import RouteServer

        self.config, self.seed = config, int(seed)
        fed, ses = config["federation"], config["session"]
        self.clients, self.clusters = int(fed["clients"]), int(fed["clusters"])
        self.sketch_dim = int(ses["sketch_dim"])
        self.schedule = generator.Schedule(
            self.clients, int(round(mix["wave_frac"] * self.clients)),
            int(mix.get("draws", 1)))
        axis = ses["mesh_axis"]
        mesh = Mesh(np.array(jax.devices()[:int(config["chips"])]), (axis,))
        self.devices = list(mesh.devices.flat)
        self.session = AggregationSession(
            self.clients, sketch_dim=self.sketch_dim,
            seed=generator.session_seed(seed), mesh=mesh, client_axis=axis)
        self.server = RouteServer(self.session)
        self.truth = None
        self.draws = []
        self.waves = 0
        self._gather = jax.jit(lambda params, idx: jax.tree_util.tree_map(
            lambda leaf: leaf[idx], params))
        self.reset_rounds()

    def memory_peaks(self) -> list:
        """``peak_bytes_in_use`` of each chip, in the mesh's order."""
        return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in self.devices]


class MeshRefresh(refresh.Refresh):
    def window(self, seconds: float) -> dict:
        result = super().window(seconds)
        result["notes"]["memory_peak_bytes_per_chip"] = \
            self.fed.memory_peaks()
        return result


def make(config: dict, mix: dict, seed: int) -> MeshRefresh:
    return MeshRefresh(MeshFederation(config, seed, mix), mix)
