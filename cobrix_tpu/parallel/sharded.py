"""Sharded columnar decode: the multi-chip data-parallel decode plane.

Replaces the reference's executor-side scan (`CobolScanners.
buildScanForVarLenIndex`, CobolScanners.scala:38 — one task per index
entry, each decoding records sequentially) with ONE jitted XLA program
whose batch axis is sharded over a device mesh: every chip decodes its
shard of the `[batch, record_len]` byte matrix simultaneously. Decode is
embarrassingly parallel so the program contains no collectives; the
`decode_stats` aggregation shows where XLA inserts psum-style reductions
over the mesh (record counts / validity totals), the analogue of the
reference's driver-side index statistics (IndexBuilder.scala:216).
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ..copybook.copybook import Copybook
from ..reader.columnar import (ColumnarDecoder, DecodedBatch,
                               _decoder_build_lock)
from .mesh import batch_sharding, data_mesh, pad_batch_to_multiple


def resolve_device_backend(backend: Optional[str]) -> str:
    """Map the default ("auto") device backend to the platform: the fused
    Pallas kernel on real TPU (the production decode plane), the plain
    XLA program elsewhere (interpret-mode pallas on CPU is a parity tool,
    not a fast path). An explicit "jax"/"pallas" wins."""
    if backend not in (None, "auto"):
        return backend
    import jax

    # a backend that cannot initialise raises here: picking the plain
    # program for it would only move the failure to the first decode
    return "pallas" if jax.default_backend() == "tpu" else "jax"


class ShardedColumnarDecoder(ColumnarDecoder):
    """ColumnarDecoder whose jax path shards the batch axis over a mesh.

    The decode program is identical to the single-chip one
    (`build_jax_decode_fn`); only the shardings differ — GSPMD partitions
    the computation, which is the point: no per-device code, no explicit
    communication, the mesh layout is declarative. With backend="pallas"
    (the default on TPU) the numeric plane runs the fused Pallas kernel,
    shard_map-ped over the mesh so each chip decodes its own batch shard.
    """

    def __init__(self, copybook: Copybook,
                 mesh=None,
                 active_segment: Optional[str] = None,
                 select=None,
                 backend: Optional[str] = None):
        super().__init__(copybook, active_segment=active_segment,
                         backend=resolve_device_backend(backend),
                         select=select)
        self.mesh = mesh if mesh is not None else data_mesh()
        self._stats_fn = None

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def _mesh_bucket(self, n: int) -> int:
        """Batch padding target: the jit bucket, rounded so the global
        batch divides evenly over the mesh (shard_map requires it)."""
        nd = self.n_devices
        bucket = max(self._bucket_size(n), nd)
        return -(-bucket // nd) * nd

    def device_program(self):
        """The sharded decode as an ops.device.DeviceProgram."""
        if self._jax_fn is None:
            with _decoder_build_lock:
                if self._jax_fn is None:
                    sharding = batch_sharding(self.mesh)
                    self._jax_fn = self._read_program(
                        self.build_jax_decode_fn(mesh=self.mesh),
                        in_shardings=sharding,
                        # every output's leading axis is the record axis;
                        # keep the results distributed — transfers gather
                        # only what the host materializes
                        out_shardings=sharding)
        return self._jax_fn

    def _decode_jax(self, arr: np.ndarray) -> Dict[int, dict]:
        x, n = self.put(arr)
        program = self.device_program()
        return self.collect_outputs(program(x), n, points=program.points)

    def put(self, arr: np.ndarray):
        """Pad `arr` to the mesh bucket and transfer it H2D with the batch
        sharding. Returns (device_array, n) for the device-resident
        `decode_stats` path — benchmarks and pipelines that must time the
        chip's compute apart from the link."""
        import jax

        n = arr.shape[0]
        padded = pad_batch_to_multiple(arr, self._mesh_bucket(n))
        return jax.device_put(padded, batch_sharding(self.mesh)), n

    def decode_stats(self, arr, n: Optional[int] = None) -> Dict[str, int]:
        """Mesh-reduced decode statistics (record count, per-codec valid
        counts). The reductions cross the shard boundary, so XLA lowers
        them to all-reduce collectives over ICI — the only cross-chip
        traffic the decode plane needs (SURVEY.md §2.5). Pass a host
        [n, extent] array, or a device-resident padded batch from `put`
        together with its `n`."""
        import jax
        import jax.numpy as jnp

        if self._stats_fn is None:
            from ..ops.device import DeviceProgram

            decode_all = self.build_jax_decode_fn(mesh=self.mesh)
            groups = self.kernel_groups

            def stats(data, n):
                # int32 accumulators: TPUs have no native int64 — keep the
                # Mosaic int32 discipline in the stats program too (counts
                # stay well under 2^31 per call)
                outs = decode_all(data)
                # mask batch padding: all-zero pad rows decode as VALID
                # zeros for the binary codecs and would inflate the counts
                live = jnp.arange(data.shape[0], dtype=jnp.int32) < n
                total_valid = jnp.zeros((), dtype=jnp.int32)
                per_group = {}
                for gi, g in enumerate(groups):
                    # strings and host-fallback groups have no such plane
                    valid = decode_all.group_planes(outs, gi).valid
                    if valid is not None and valid.dtype == jnp.bool_:
                        v = (valid & live[:, None]).sum(dtype=jnp.int32)
                        per_group[f"{g.codec.value}_w{g.width}"] = v
                        total_valid = total_valid + v
                return {"records": n,
                        "valid_values": total_valid, **per_group}

            sharding = batch_sharding(self.mesh)
            self._stats_fn = DeviceProgram(
                stats, interpreted=decode_all.interpret,
                device_groups=decode_all.device_groups,
                in_shardings=(sharding, None))

        if n is None:
            arr, n = self.put(arr)
        out = jax.device_get(self._stats_fn(arr, np.int32(n)))
        return {k: int(v) for k, v in out.items()}


def sharded_decode(copybook: Copybook, data, mesh=None,
                   lengths: Optional[np.ndarray] = None) -> DecodedBatch:
    """One-shot helper: decode bytes/[N, rs] uint8 across the mesh."""
    dec = ShardedColumnarDecoder(copybook, mesh=mesh)
    return dec.decode(data, lengths=lengths)
