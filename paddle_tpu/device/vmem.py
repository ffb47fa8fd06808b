"""Per-generation TPU VMEM budgets — the single source of truth for
kernel VMEM limits.

Every hand-tuned Pallas kernel in the repo caps its scoped-VMEM use via
``compiler_params(vmem_limit_bytes=...)``. Those caps used to be magic
``100 * 1024 * 1024`` literals scattered across the kernel modules; the
geometry pass of ``paddle_tpu.analysis`` flags any such literal
(rule ``G-MAGIC``) and this module is where the number actually comes
from: the physical VMEM of the target generation minus a fixed reserve
for Mosaic's own scratch (spills, semaphores, pipelining bookkeeping).

Physical VMEM per TensorCore by generation (v2-v4 from the public TPU
system architecture docs; v5e confirmed empirically by the r5 kernel
bring-up — the repo's streaming kernels run with a 100MB cap on v5e):

    v2 / v3 : 16 MiB
    v4+     : 128 MiB (v4, v5e, v5p, v6e)

Off-TPU (CPU interpret mode) the budget is irrelevant to execution but
the analyzer still validates against the DEFAULT serving generation so
CI catches geometry that would not fit the chip.
"""
from __future__ import annotations

__all__ = [
    "MiB", "GiB", "VMEM_BUDGET_BYTES", "VMEM_RESERVE_BYTES",
    "DEFAULT_GENERATION", "KERNEL_VMEM_LIMIT_BYTES",
    "MOSAIC_DEFAULT_VMEM_LIMIT_BYTES", "vmem_budget_bytes",
    "HBM_BUDGET_BYTES", "HBM_RESERVE_BYTES", "hbm_budget_bytes",
    "detect_generation",
]

MiB = 1 << 20
GiB = 1 << 30

#: physical VMEM bytes per TensorCore, by TPU generation
VMEM_BUDGET_BYTES = {
    "v2": 16 * MiB,
    "v3": 16 * MiB,
    "v4": 128 * MiB,
    "v5e": 128 * MiB,
    "v5p": 128 * MiB,
    "v6e": 128 * MiB,
}

#: physical HBM bytes per chip, by TPU generation (public TPU system
#: architecture docs; the MEMORY pass of ``paddle_tpu.analysis`` checks
#: a program's static peak-live-bytes bound against this table, so
#: "this 13B config OOMs on v5e" is a CPU-side lint finding instead of
#: a burned chip session)
HBM_BUDGET_BYTES = {
    "v2": 8 * GiB,
    "v3": 16 * GiB,
    "v4": 32 * GiB,
    "v5e": 16 * GiB,
    "v5p": 95 * GiB,
    "v6e": 32 * GiB,
}

#: HBM held back from the analyzer's budget: the XLA runtime's own
#: allocations (executables, infeed/outfeed, framework scratch) that a
#: program's buffer liveness never sees
HBM_RESERVE_BYTES = 1 * GiB

#: headroom left to the Mosaic compiler for its own scratch — register
#: spills, DMA semaphores, pipelining bookkeeping — on top of what the
#: kernel's declared blocks/scratch consume
VMEM_RESERVE_BYTES = 28 * MiB

#: the serving generation the hand-tuned kernel geometry targets
DEFAULT_GENERATION = "v5e"

#: the vmem_limit_bytes every repo Pallas kernel declares: generation
#: budget minus the Mosaic reserve (= the historical 100 MiB cap, now
#: derived instead of hard-coded)
KERNEL_VMEM_LIMIT_BYTES = (
    VMEM_BUDGET_BYTES[DEFAULT_GENERATION] - VMEM_RESERVE_BYTES)

#: what a pallas_call gets when it declares NO vmem_limit_bytes — the
#: conservative scoped-VMEM default of the XLA:TPU compiler
#: (xla_tpu_scoped_vmem_limit_kib = 16384)
MOSAIC_DEFAULT_VMEM_LIMIT_BYTES = 16 * MiB

def detect_generation(default: str = DEFAULT_GENERATION) -> str:
    """TPU generation of the attached accelerator. Off-TPU the static
    analyses validate against ``default``, the serving target they are
    asked about (CPU CI lints for the chip it will deploy on); ON a TPU
    the device must be in ``device.chip.CHIPS`` — an unknown
    ``device_kind`` raises instead of being budgeted as another chip."""
    import jax

    from .chip import chip_spec

    if jax.default_backend() != "tpu":
        return default
    return chip_spec(jax.devices()[0]).generation


def vmem_budget_bytes(generation: str | None = None) -> int:
    """Physical VMEM budget for ``generation`` (auto-detected when
    None); a generation that is not in the table raises."""
    gen = generation or detect_generation()
    return VMEM_BUDGET_BYTES[gen]


def hbm_budget_bytes(generation: str | None = None) -> int:
    """Usable HBM for ``generation`` (auto-detected when None): the
    physical capacity minus the runtime reserve; a generation that is
    not in the table raises."""
    gen = generation or detect_generation()
    return HBM_BUDGET_BYTES[gen] - HBM_RESERVE_BYTES
