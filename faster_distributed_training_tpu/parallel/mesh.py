"""Device-mesh bootstrap.

The reference boots NCCL process groups three ways (utils.py:13-30:
env:// rendezvous, torchrun-provided rank, shared-file rendezvous).  The TPU
equivalent is `jax.distributed.initialize(coordinator, num_processes,
process_id)` once per host, then ONE `Mesh` over all global devices; data /
fully-sharded / tensor / sequence parallelism are just axes of that mesh.

Axis naming convention used framework-wide:
  "dp"   — data parallel (batch sharded, grads psum'd by XLA)
  "fsdp" — fully-sharded data parallel (batch AND params/opt-state sharded;
           ZeRO-3; XLA turns grad psum into reduce_scatter + all_gather)
  "tp"   — tensor parallel (attention heads / MLP hidden sharded)
  "sp"   — sequence/context parallel (ring attention, ops/ring_attention.py)
  "pp"   — pipeline parallel (encoder LAYERS staged across slices; the
           stage-boundary activation rotation is the only per-step
           collective, so pp tolerates the slowest links and is the
           PREFERRED axis to span DCN on multi-slice pods —
           parallel/pipeline.py.  Since r23 pp is also a RESIDENCY
           axis: stage-owned params and optimizer state are physically
           sharded over pp (parallel/sharding.py pp-residency rules),
           so per-chip HBM for those tiers scales ~1/S with pipeline
           depth and pp composes multiplicatively with tp/ZeRO.)

AXIS_ALIASES is the ONE canonical alias table (r11 satellite): every
surface that names a mesh axis — ``--mesh`` parsing, ``resolve_attention``
auto-routing, ``apply_tp_rules``, the shard_map fallbacks in
``build_model`` — goes through ``canonical_axis`` so ``--mesh
dp=4,model=2`` and ``--mesh dp=4,tp=2`` are the same mesh and no layer
can disagree about what the model axis is called.
"""

from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# canonical-name ← accepted spellings.  Unknown names pass through
# unchanged (exotic axes stay usable), but the four canonical roles each
# accept the common alternative spellings, so the TP rules (which match
# the LITERAL string "tp") and the sequence-parallel ops (literal "sp")
# always see the canonical name regardless of what the CLI was given.
AXIS_ALIASES = {
    "dp": "dp", "data": "dp", "batch": "dp",
    "fsdp": "fsdp", "zero": "fsdp", "zero3": "fsdp",
    "tp": "tp", "model": "tp", "mp": "tp", "tensor": "tp",
    "sp": "sp", "seq": "sp", "sequence": "sp", "context": "sp",
    "pp": "pp", "pipe": "pp", "pipeline": "pp", "stage": "pp",
}

# ICI speed rank for the auto device-assignment policy: higher = placed
# on a faster (more-minor) mesh axis.  Model/sequence axes carry the
# per-layer collectives (psum at every FFN/projection boundary, the
# ring's per-step ppermute), data axes one grad psum per step — so tp
# gets the fastest links, dp the slowest.  pp ranks BELOW dp: a pipeline
# stage boundary moves one [microbatch, L, d_model] activation per tick
# point-to-point (collective-permute), the cheapest per-step traffic of
# any axis, so pp is placed outermost and is the preferred axis to span
# DCN between slices on multi-slice pods (_ici_device_mesh).
_AXIS_SPEED = {"pp": -1, "dp": 0, "fsdp": 1, "sp": 2, "tp": 3}


def canonical_axis(name: str) -> str:
    """Canonical spelling of a mesh-axis name (AXIS_ALIASES)."""
    return AXIS_ALIASES.get(str(name).strip().lower(), str(name).strip())


def canonical_axes(axes: Sequence[str]) -> Tuple[str, ...]:
    out = tuple(canonical_axis(a) for a in axes)
    if len(set(out)) != len(out):
        raise ValueError(f"mesh axes {tuple(axes)} collapse to duplicate "
                         f"canonical names {out} (see AXIS_ALIASES)")
    return out


def axis_size(mesh: Optional[Mesh], name: str) -> int:
    """Size of canonical axis `name` in `mesh` (1 when absent/None)."""
    if mesh is None:
        return 1
    name = canonical_axis(name)
    return int(mesh.shape[name]) if name in mesh.axis_names else 1


def tp_size(mesh: Optional[Mesh]) -> int:
    return axis_size(mesh, "tp")


def sp_size(mesh: Optional[Mesh]) -> int:
    return axis_size(mesh, "sp")


def pp_size(mesh: Optional[Mesh]) -> int:
    return axis_size(mesh, "pp")


def seq_parallel_axis(mesh: Optional[Mesh]) -> Tuple[Optional[str], int]:
    """(axis_name, size) the sequence-parallel ops (ring/ulysses) and the
    sequence-sharded activation regions should use: a dedicated "sp"
    axis when present at size > 1, else the "tp" axis (Megatron-style
    sequence parallelism rides the tensor-parallel group), else
    (None, 1).  The ONE policy resolve_attention, build_model and the
    model's activation annotations all share."""
    for name in ("sp", "tp"):
        n = axis_size(mesh, name)
        if n > 1:
            return name, n
    return None, 1


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    axes: Tuple[str, ...]
    shape: Tuple[int, ...]

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


def initialize_distributed(coordinator: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap; replaces MASTER_ADDR/MASTER_PORT + init_process_group.

    No-op for single-process runs.  Arguments default from the environment
    (FDT_COORDINATOR, FDT_NUM_PROCESSES, FDT_PROCESS_ID), mirroring how
    torchrun feeds rank/world-size via env vars (utils.py:20-23) — but with
    no fixed hard-coded port (reference pins 12355, utils.py:15).
    """
    coordinator = coordinator or os.environ.get("FDT_COORDINATOR")
    if num_processes is None:
        num_processes = int(os.environ.get("FDT_NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("FDT_PROCESS_ID", "0"))
    if num_processes > 1:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)


def _ici_device_mesh(shape: Tuple[int, ...],
                     axes: Tuple[str, ...]) -> np.ndarray:
    """ICI-aware device assignment for a TPU mesh (SNIPPETS [1]).

    `mesh_utils.create_device_mesh` assigns later mesh dims to
    physically nearer chips, so the axes are permuted SLOWEST-first by
    `_AXIS_SPEED` (dp outermost, tp innermost = fastest links) before
    construction and transposed back to the caller's order after — the
    "tp on the fastest axis" auto policy.  Multi-process pods factor the
    slowest data axis over DCN via `create_hybrid_device_mesh`.  The
    caller asks only for shapes that use every device; a request the
    topology tools cannot serve RAISES with the cause in it — a mesh
    laid out by plain reshape instead would train, slower, without a
    word."""
    from jax.experimental import mesh_utils
    perm = sorted(range(len(axes)),
                  key=lambda i: (_AXIS_SPEED.get(axes[i], -1), i))
    pshape = tuple(shape[i] for i in perm)
    try:
        pc = jax.process_count()
        if pc > 1:
            # factor the process count out of the slowest eligible axis
            # that divides it — that axis spans slices over DCN,
            # everything else stays inside a slice's ICI.  Eligible:
            # pp FIRST (it sorts outermost at speed -1 — a stage
            # boundary moves one point-to-point activation per tick, the
            # cheapest traffic to put on the slow links), then dp/fsdp
            # (one grad reduction per step).  tp/sp stay ineligible:
            # letting them span DCN would put the per-layer
            # model-parallel collectives on the slowest links, inverting
            # the _AXIS_SPEED policy — a mesh whose pp/data axes can't
            # absorb the process count is an error.
            paxes = [axes[i] for i in perm]
            dcn = [1] * len(pshape)
            for j, d in enumerate(pshape):
                if (paxes[j] in ("pp", "dp", "fsdp")
                        and d % pc == 0 and d >= pc):
                    dcn[j] = pc
                    break
            else:
                raise ValueError(
                    f"no pp/dp/fsdp axis of {dict(zip(axes, shape))} is "
                    f"divisible by the {pc} processes (tp/sp never span "
                    f"DCN)")
            ici = list(pshape)
            ici[j] //= pc
            dev = mesh_utils.create_hybrid_device_mesh(
                tuple(ici), tuple(dcn))
        else:
            dev = mesh_utils.create_device_mesh(pshape)
    except Exception as e:
        raise RuntimeError(
            f"cannot build an ICI-aware device mesh for "
            f"{dict(zip(axes, shape))} over {jax.device_count()} "
            f"{jax.devices()[0].device_kind} device(s): {e}") from e
    return np.transpose(dev, np.argsort(perm))


def make_mesh(axes: Sequence[str] = ("dp",),
              shape: Sequence[int] = (),
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh. Empty `shape` auto-sizes: one unsized axis absorbs all devices.

    Axis names are canonicalized through AXIS_ALIASES (``--mesh
    dp=4,model=2`` == ``dp=4,tp=2``).  On TPU with default devices the
    device assignment is ICI-aware (`_ici_device_mesh`: tp on the
    fastest links, hybrid ICI×DCN on pods — SNIPPETS [1]); everywhere
    else (CPU simulation, explicit device lists) it is the plain
    row-major reshape, whose LAST axis is still the fastest-varying —
    so ``dp=4,tp=2`` groups tp pairs on adjacent devices either way.

    Single-process only: a shape smaller than the visible device count
    uses the FIRST prod(shape) devices — the CUDA_VISIBLE_DEVICES-
    narrowing analog (run_distributed.sh:2), e.g. `--mesh dp=1` on an
    8-chip host.  Multi-host runs keep the exact-count requirement: a
    mesh built from a subset would exclude some processes' addressable
    devices and fail far later inside batch assembly.

    Examples:
      make_mesh()                          -> all devices on "dp"
      make_mesh(("dp","tp"), (4, 2))       -> 4x2 (data, model) mesh
      make_mesh(("fsdp",))                 -> all devices fully-sharded
    """
    explicit_devices = devices is not None
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    axes = canonical_axes(axes)
    if not shape:
        shape = (n,) + (1,) * (len(axes) - 1)
    shape = tuple(shape)
    if len(shape) != len(axes):
        raise ValueError(f"mesh axes {axes} vs shape {shape} rank mismatch")
    want = int(np.prod(shape))
    if want > n or (want < n and jax.process_count() > 1):
        raise ValueError(f"mesh shape {shape} needs {want} devices, "
                         f"have {n}"
                         + (" across all hosts — per-host narrowing is "
                            "not supported in multi-process runs"
                            if jax.process_count() > 1 else ""))
    if want < n:
        warnings.warn(f"mesh shape {shape} uses {want} of {n} visible "
                      f"devices; the remaining {n - want} idle",
                      stacklevel=2)
    if (not explicit_devices and want == n
            and devices[0].platform == "tpu"):
        dev_array = _ici_device_mesh(shape, axes)
    else:
        dev_array = np.asarray(devices[:want]).reshape(shape)
    return Mesh(dev_array, axes)


def local_batch_slice(global_batch: int, mesh: Mesh) -> Tuple[int, int]:
    """(per-host batch, host offset) for building per-host sharded loaders.

    Replaces torch's DistributedSampler (resnet50_test.py:331): each host
    loads only its slice of the global batch; `jax.make_array_from_process_local_data`
    assembles the global array.
    """
    n_proc = jax.process_count()
    if global_batch % n_proc:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"{n_proc} processes")
    per = global_batch // n_proc
    return per, per * jax.process_index()
