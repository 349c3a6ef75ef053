"""Records what the program's device simulator returned on the timed path.

`DeviceTap` wraps `JaxDES.batch_genome_makespan`, the entry through which
the GA's fitness batches and the fleet's repair candidates reach the
jitted DES, and keeps a copy of each call's genomes, pair lists, capacity
mask and returned makespans.  The wrapper adds a few small array copies to
each call and changes nothing the program computes.  The comparison with
the reference reads these records after the window.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class DeviceCall:
    dag: object                 # the CommDAG the simulator was built for
    genomes: np.ndarray         # (S, E) circuits per undirected pair
    edge_u: np.ndarray
    edge_v: np.ndarray
    mask: np.ndarray | None     # (P, P) capacity factor, None = healthy
    makespans: np.ndarray       # (S,) as the device returned them
    feasible: np.ndarray        # (S,)

    def topology(self, row: int) -> np.ndarray:
        """Row `row` as a (P, P) circuit matrix scaled by the mask."""
        P = self.dag.cluster.num_pods
        x = np.zeros((P, P))
        x[self.edge_u, self.edge_v] = self.genomes[row]
        x[self.edge_v, self.edge_u] = self.genomes[row]
        return x * self.mask if self.mask is not None else x


class DeviceTap:
    """Install with `with DeviceTap() as tap:`; `tap.calls` holds the
    records while `tap.recording` is on."""

    def __init__(self):
        from repro.core.des_jax import JaxDES
        self._cls = JaxDES
        self._orig = JaxDES.batch_genome_makespan
        self.calls: list[DeviceCall] = []
        self.recording = False

    def __enter__(self) -> "DeviceTap":
        tap, orig = self, self._orig

        def batch_genome_makespan(jd, genomes, edge_u, edge_v, mask=None):
            ms, feas = orig(jd, genomes, edge_u, edge_v, mask=mask)
            if tap.recording:
                tap.calls.append(DeviceCall(
                    dag=jd.problem.dag,
                    genomes=np.array(genomes, dtype=np.int64),
                    edge_u=np.array(edge_u, dtype=np.int64),
                    edge_v=np.array(edge_v, dtype=np.int64),
                    mask=None if mask is None else np.array(
                        mask, dtype=np.float64),
                    makespans=np.array(ms, dtype=np.float64),
                    feasible=np.array(feas, dtype=bool)))
            return ms, feas

        self._cls.batch_genome_makespan = batch_genome_makespan
        return self

    def __exit__(self, *exc) -> bool:
        self._cls.batch_genome_makespan = self._orig
        return False

    def sample(self, rng: np.random.Generator, count: int
               ) -> list[tuple[DeviceCall, int]]:
        """`count` distinct (call, row) evaluations drawn from `rng`, one
        row per distinct (DAG, mask, genome)."""
        seen, rows = set(), []
        for call in self.calls:
            mkey = b"" if call.mask is None else call.mask.tobytes()
            for r in range(len(call.genomes)):
                key = (id(call.dag), mkey, call.genomes[r].tobytes())
                if key not in seen:
                    seen.add(key)
                    rows.append((call, r))
        if len(rows) <= count:
            return rows
        pick = rng.choice(len(rows), size=count, replace=False)
        return [rows[i] for i in sorted(pick)]
