"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state, so tests/benches keep their 1-CPU world while
the dry-run (which sets xla_force_host_platform_device_count=512 before
any import) builds the real topology.

Target hardware: TPU v5e pods — 256 chips/pod, (16, 16) ICI torus;
multi-pod adds a leading 'pod' axis over DCN.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e per-chip constants used by the roofline (benchmarks/roofline.py)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link


def data_parallel_size(mesh) -> int:
    """Number of data-parallel shards of the global batch: the product
    of the mesh's 'pod' and 'data' axes (1 when no mesh).  Accepts any
    duck-typed object exposing a ``.shape`` mapping, so the engine and
    per-host plan validation share one definition of the data width."""
    if mesh is None:
        return 1
    shape = dict(mesh.shape)
    n = 1
    for axis in ("pod", "data"):
        n *= int(shape.get(axis, 1))
    return max(n, 1)


def _row_blocks_by_process(indices_map, n_rows: int):
    """{process_index: set of data-slot rows it owns} from a
    ``devices_indices_map`` of a length-``n_rows`` batch axis."""
    per: dict = {}
    for dev, idx in indices_map.items():
        sl = idx[0] if idx else slice(0, n_rows)
        start = sl.start or 0
        stop = n_rows if sl.stop is None else sl.stop
        per.setdefault(dev.process_index, set()).update(
            range(start, stop))
    return per


def check_per_host_row_blocks(per_process, n_rows: int,
                              process_count: int):
    """Pure check behind :func:`assert_per_host_row_blocks` (testable
    with synthetic layouts): process ``p`` must own exactly the
    contiguous slot block ``[p*n/N, (p+1)*n/N)`` — the layout the
    per-host loader samples (process p contributes rows
    ``[p*B/N, (p+1)*B/N)`` of every global batch)."""
    if n_rows % process_count:
        raise ValueError(
            f"data-parallel width {n_rows} does not divide across "
            f"{process_count} host processes — per-host feeding "
            f"cannot assign whole row blocks")
    per = n_rows // process_count
    for p in range(process_count):
        want = list(range(p * per, (p + 1) * per))
        got = sorted(per_process.get(p, ()))
        if got != want:
            raise ValueError(
                f"process {p} owns data-axis slots {got} but per-host "
                f"feeding requires the contiguous block "
                f"[{want[0]}, {want[-1] + 1}) in process order — this "
                f"mesh's device order breaks the loader's row-block "
                f"assumption (jax.make_mesh layouts satisfy it; custom "
                f"meshes must keep each process's devices contiguous "
                f"along the data axes)")


def assert_per_host_row_blocks(mesh, process_count: int | None = None):
    """Assert — from the actual ``NamedSharding``, not a mesh-builder
    heuristic — that each process owns one contiguous, process-ordered
    block of the batch (data) axis, so ``per_host=True`` feeding is
    safe on this mesh.  No-op for single-process runs or ``mesh=None``;
    raises ``ValueError`` on custom meshes whose device order would
    silently misassign rows."""
    nproc = (jax.process_count() if process_count is None
             else process_count)
    if mesh is None or nproc <= 1:
        return
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    n = data_parallel_size(mesh)
    axes = tuple(a for a in ("pod", "data") if a in dict(mesh.shape))
    sharding = NamedSharding(mesh, P(axes if axes else None))
    per = _row_blocks_by_process(sharding.devices_indices_map((n,)), n)
    check_per_host_row_blocks(per, n, nproc)


def auto_mesh(dims, names):
    """A device mesh whose axes are all ``Auto``.  The programs here
    place state with ``NamedSharding`` in/out shardings and sharding
    constraints, and leave every intermediate to GSPMD propagation;
    ``jax.make_mesh``'s default ``Explicit`` axes would instead type
    each intermediate's sharding (the embedding gather of a
    ``P("model", "data")`` table by ``data``-sharded tokens would then
    name ``data`` twice)."""
    return jax.make_mesh(tuple(dims), tuple(names),
                         axis_types=(AxisType.Auto,) * len(dims))


def gspmd_mesh(mesh):
    """``mesh`` with every axis ``Auto`` — the identity for meshes built
    here, so a caller may also hand in ``jax.make_mesh``'s default
    explicit mesh.  Anything that is not a ``Mesh`` (``None``, shape-only
    stand-ins) passes through."""
    if (not isinstance(mesh, jax.sharding.Mesh)
            or all(t == AxisType.Auto for t in mesh.axis_types)):
        return mesh
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names,
                             axis_types=(AxisType.Auto,)
                             * len(mesh.axis_names))


def make_launch_mesh(spec: str | None, *, distributed: bool = False):
    """The launcher's mesh from a ``--mesh`` spec ("DxM" data x model,
    or "PxDxM" pod x data x model), or the default multi-process
    topology — pure data parallelism over every global device — when
    ``distributed`` and no spec.  ``None`` (single-process, no spec)
    keeps the mesh-less fast path."""
    if spec:
        dims = [int(x) for x in spec.split("x")]
        names = ("data", "model")[:len(dims)] if len(dims) == 2 \
            else ("pod", "data", "model")
        return auto_mesh(dims, names)
    if distributed:
        return auto_mesh((jax.device_count(), 1), ("data", "model"))
    return None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2,
                   multi_pod: bool = False):
    """Small mesh for CI-scale dry-run tests (8 host devices)."""
    if multi_pod:
        return auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return auto_mesh((n_data, n_model), ("data", "model"))
