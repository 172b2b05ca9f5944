"""Device-mesh layer for the sweep grids — mesh, padding, placement.

The sweep evaluation surface is a (batch × policy × scenario) grid of
*independent* cells, which makes it embarrassingly shardable: this module
owns how that grid is laid out across devices so ``core/sweep.py`` can stay
about orchestration.

**Mesh.** ``grid_mesh()`` builds (and caches — one ``jax.make_mesh`` per
process shape, not per sweep call) a 3D mesh over all live devices with axes

    ("data", "grid", "policy")

where ``data`` carries the batched sweep axis (fleet | workflow | capacity),
``grid`` carries the scenario axis — the largest axis in every paper-style
grid, which the previous 1D layout left fully replicated on every device —
and ``policy`` optionally splits the allocation-policy stack.  By default
the policy axis is a singleton (dp=1): arrays never shard over a size-1
axis, so every pre-3D program is bit-identical to the old 2D layout.
Callers opt in with ``shard="3d"`` (near-cubic ``mesh_shape_3d`` factoring:
8 devices → 2×2×2), ``REPRO_SWEEP_POLICY_DEVICES=<dp>`` (explicit width),
or ``REPRO_SWEEP_MESH3D=1`` (global switch).  With dp>1 the streaming
kernel dispatches each device's policy *block* via one ``lax.switch`` on
``jax.lax.axis_index("policy")``; the non-divisible policy count pads with
repeats of policy row 0 (name-tuple padding — stripped host-side like every
other padded axis).  The remaining ``num_devices/dp`` factor splits
near-square with the larger factor on ``grid`` (8 devices, dp=1 → 2 × 4),
so scenario-major grids parallelize even when the batch axis is tiny.

**Divisibility.** A sharded axis must divide its mesh axis.  Instead of the
old silent whole-axis replication fallback (which forfeits *all*
parallelism — 6 fleets on 4 devices ran 4× redundantly), non-divisible axes
are **padded** to the next multiple with copies of row 0 (always-valid
cells, reusing the ``active``-mask idiom of never letting filler produce
NaNs) and the padded rows are stripped on the host side — metrics are
identical to the unpadded grid (``tests/test_sharding.py``).

**Escape hatch.** ``REPRO_SWEEP_SHARD=0`` in the environment forces the
single-device (unsharded) path everywhere, whatever the device count — the
documented debugging switch when a mesh-related failure needs to be
isolated from the grid math.

**Host-device forcing.** On CPU hosts the multi-device path is exercised by
forcing XLA to expose fake host devices (``--xla_force_host_platform_
device_count=N`` — the XLA-flag-dictionary idiom of the serving stacks this
repo's SNIPPETS reference).  ``host_device_env`` builds a subprocess
environment with N forced devices (how the scaling benchmark and the
sharding tests spawn 1/2/4/8-device workers); ``force_host_device_count``
sets the flag in-process and refuses to run once the backend is already
initialized, because the flag is read exactly once.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"     # batched sweep axis: fleet | workflow | capacity
GRID_AXIS = "grid"     # scenario axis
POLICY_AXIS = "policy"  # allocation-policy axis (the (P, N) state stack rows)

SHARD_ENV = "REPRO_SWEEP_SHARD"
MESH3D_ENV = "REPRO_SWEEP_MESH3D"          # "1": auto near-cubic policy axis
POLICY_ENV = "REPRO_SWEEP_POLICY_DEVICES"  # explicit dp override
_FORCE_FLAG = "--xla_force_host_platform_device_count"


def shard_env_enabled() -> bool:
    """False iff ``REPRO_SWEEP_SHARD=0`` (or ``false``/``off``) is set."""
    return os.environ.get(SHARD_ENV, "").lower() not in ("0", "false", "off")


def should_shard(flag: bool | None = None) -> bool:
    """Resolve one sweep call's sharding decision.

    ``flag=False`` always wins; the ``REPRO_SWEEP_SHARD=0`` escape hatch
    wins next; otherwise shard exactly when more than one device is live
    (on a single device the sharded and unsharded programs are the same
    placement, and routing through the plain jit keeps single-device
    results bit-identical by construction).
    """
    if flag is False:
        return False
    if not shard_env_enabled():
        return False
    return jax.device_count() > 1


def mesh_shape(num_devices: int) -> tuple[int, int]:
    """Factor ``num_devices`` into (data, grid) mesh dims, near-square with
    the larger factor on ``grid`` — the scenario axis dominates paper-style
    grids, so it gets the wider slice of the machine."""
    n = int(num_devices)
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    dd = max(k for k in range(1, math.isqrt(n) + 1) if n % k == 0)
    return dd, n // dd


def mesh_shape_3d(num_devices: int) -> tuple[int, int, int]:
    """Factor ``num_devices`` into (data, grid, policy) mesh dims,
    near-cubic: the policy axis takes the largest divisor whose cube fits
    (8 → 2×2×2, 64 → 4×4×4), the remainder splits near-square with the
    larger factor on ``grid`` exactly as in the 2D layout.  Primes land
    entirely on ``grid`` (7 → 1×7×1) — the policy axis degrades to
    unsharded rather than starving the scenario axis."""
    n = int(num_devices)
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    dp = max(k for k in range(1, n + 1) if n % k == 0 and k ** 3 <= n)
    dd, dg = mesh_shape(n // dp)
    return dd, dg, dp


@functools.lru_cache(maxsize=None)
def _cached_mesh(dd: int, dg: int, dp: int) -> Mesh:
    # Auto axes: the host-side strip of padded rows (``x[:b, :p, :w]``) is
    # plain indexing, which explicit-sharding axes (``make_mesh``'s default)
    # refuse without an ``out_sharding``.
    return jax.make_mesh(
        (dd, dg, dp), (DATA_AXIS, GRID_AXIS, POLICY_AXIS),
        axis_types=(AxisType.Auto,) * 3,
    )


def grid_mesh(
    num_devices: int | None = None, policy_devices: int = 1
) -> Mesh:
    """The cached ``("data", "grid", "policy")`` sweep mesh over all live
    devices.

    ``policy_devices`` (dp) is the policy-axis width; the remaining
    ``num_devices / dp`` factor splits near-square over (data, grid) as
    before.  The default ``dp=1`` is the 2D layout with a singleton third
    axis — arrays never shard over a size-1 axis, so every pre-3D program
    is unchanged.  The mesh is built once per shape and cached for the life
    of the process — the device topology cannot change after backend
    initialization, and ``jax.make_mesh`` is too expensive for a per-sweep
    rebuild.
    """
    n = jax.device_count() if num_devices is None else int(num_devices)
    dp = int(policy_devices)
    if dp < 1 or n % dp:
        raise ValueError(
            f"policy_devices={dp} must divide the device count {n}"
        )
    dd, dg = mesh_shape(n // dp)
    return _cached_mesh(dd, dg, dp)


def policy_mesh_devices(flag=None) -> int:
    """Resolve one sweep call's policy-axis device count (dp).

    ``dp=1`` — the 2D layout — unless the caller opts in: ``shard="3d"``
    requests the near-cubic ``mesh_shape_3d`` factoring, the
    ``REPRO_SWEEP_POLICY_DEVICES`` env var pins an explicit dp, and
    ``REPRO_SWEEP_MESH3D=1`` turns the near-cubic factoring on globally.
    Whenever sharding itself is off (``should_shard``), dp is 1.
    """
    if not should_shard(flag):
        return 1
    n = jax.device_count()
    env_dp = os.environ.get(POLICY_ENV, "")
    if env_dp:
        dp = int(env_dp)
        if dp < 1 or n % dp:
            raise ValueError(
                f"{POLICY_ENV}={dp} must divide the device count {n}"
            )
        return dp
    if flag == "3d" or os.environ.get(MESH3D_ENV, "").lower() in ("1", "true", "on"):
        return mesh_shape_3d(n)[2]
    return 1


def pad_axis(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    """Pad ``x`` along ``axis`` up to the next multiple of ``multiple`` by
    repeating the slice at index 0.

    Repeating a *real* row (rather than zeros) keeps every padded cell a
    well-posed simulation — no degenerate fleets, no NaN risk anywhere in
    the padded block — mirroring how ``pad_fleet`` keeps padded agent slots
    inert-but-valid.  Callers strip the rows host-side after the grid runs.
    """
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(0, 1)
    filler_shape = x.shape[:axis] + (pad,) + x.shape[axis + 1:]
    filler = jnp.broadcast_to(x[tuple(idx)], filler_shape)
    return jnp.concatenate([x, filler], axis=axis)


def pad_tree_axis(tree: Any, axis: int, multiple: int) -> Any:
    """``pad_axis`` over every leaf of a stacked pytree (Fleet / Workflow /
    CapacityConfig batches — static aux data like names passes through)."""
    return jax.tree_util.tree_map(lambda x: pad_axis(x, axis, multiple), tree)


def grid_specs(
    batch_axis: str | None, policy: bool = False
) -> tuple[tuple, PartitionSpec]:
    """(in_specs, out_spec) for one sharded streaming grid call.

    ``in_specs`` covers ``(arrivals, fleet, workflow, capacity, wspec,
    fspec)`` — pytree *prefixes*, so one spec serves every leaf of a
    stacked pytree.  ``wspec`` (a stacked ``WorkloadSpec``, the in-scan
    synthesis twin of the arrivals tensor) always shards exactly like
    arrivals: its leaves carry the same leading scenario/batch axes, just
    without the (S,) horizon axis, which the arrivals prefix specs never
    constrain anyway.  ``fspec`` (a ``FailureSpec``) is replicated except
    under ``batch_axis="failure"``, where its stacked scenario axis shards
    over ``data`` and the (shared) workload block over ``grid`` — the
    chaos axis lays out exactly like the other batched sweep axes.  With a
    batch axis, the batch shards over ``data`` and the scenario axis over
    ``grid``; the plain ``sweep`` grid has only a scenario axis, which
    shards over the *flattened* (data × grid) plane so no device idles.
    ``out_spec`` is the shared prefix for all four kernel outputs, whose
    layout is ([batch,] policy, scenario, ·); with ``policy=True`` the
    policy dim additionally shards over the third mesh axis (the kernel
    computes only its own block of policy rows per device — inputs stay
    replicated along ``policy``, each block reads the same state).
    """
    P = PartitionSpec
    pol = POLICY_AXIS if policy else None
    if batch_axis is None:
        both = (DATA_AXIS, GRID_AXIS)
        return (P(both), P(), P(), P(), P(both), P()), P(pol, both)
    arrivals = {
        "fleet": P(DATA_AXIS, GRID_AXIS),   # (F, W, S, N): per-fleet columns
        "workflow": P(GRID_AXIS),           # (W, S, N): one shared block
        "capacity": P(GRID_AXIS),
        "failure": P(GRID_AXIS),
    }[batch_axis]
    batched = P(DATA_AXIS)
    fleet = batched if batch_axis == "fleet" else P()
    workflow = batched if batch_axis == "workflow" else P()
    capacity = batched if batch_axis == "capacity" else P()
    fspec = batched if batch_axis == "failure" else P()
    return (
        (arrivals, fleet, workflow, capacity, arrivals, fspec),
        P(DATA_AXIS, pol, GRID_AXIS),
    )


def replicated(mesh: Mesh) -> NamedSharding:
    """Every-device replication — the old 1D fallback layout, kept only as
    the benchmark baseline (``benchmarks/scaling_frontier.py`` measures the
    redundant work it burns)."""
    return NamedSharding(mesh, PartitionSpec())


# -- host-device forcing (CPU multi-device harness) --------------------------


def _strip_force_flag(flags: str) -> list[str]:
    return [f for f in flags.split() if not f.startswith(_FORCE_FLAG)]


def host_device_env(
    num_devices: int, base_env: dict | None = None
) -> dict[str, str]:
    """Environment for a subprocess worker seeing ``num_devices`` forced
    host CPU devices — the one way to measure 1/2/4/8-device scaling on a
    CPU host, since the flag is consumed at backend initialization and can
    never change inside a live process."""
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    flags = _strip_force_flag(env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = " ".join(
        flags + [f"{_FORCE_FLAG}={int(num_devices)}"]
    ).strip()
    return env


def force_host_device_count(num_devices: int) -> None:
    """Set the forced-host-device flag for *this* process.

    Only effective before jax initializes its backends; once devices exist
    the flag is dead, so this raises instead of silently doing nothing.
    """
    if _backend_initialized():
        raise RuntimeError(
            "jax backends are already initialized; "
            f"{_FORCE_FLAG} must be set before the first device query "
            "(use host_device_env + a subprocess instead)"
        )
    flags = _strip_force_flag(os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = " ".join(
        flags + [f"{_FORCE_FLAG}={int(num_devices)}"]
    ).strip()


def _backend_initialized() -> bool:
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:  # private API moved: assume live, the safe answer
        return True
