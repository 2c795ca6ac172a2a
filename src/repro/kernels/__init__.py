"""Pallas kernels for the SpKAdd engine, and their launch constants."""

#: Lane count of a TPU vector register: accumulator tiles and hash tables are
#: laid out as ``(rows, LANES)`` so a dynamic row index is a sublane slice.
LANES = 128

#: VMEM the launch geometry may plan for one kernel, counting both pipeline
#: buffers of every block (Pallas double-buffers outputs as well as inputs).
#: A v5e core has 128 MiB of VMEM; Mosaic's default scoped limit is 16 MiB,
#: which would leave a 2^20-slot hash table (8 MiB, 16 MiB double-buffered)
#: no room, so the engine's kernels raise the limit explicitly.
VMEM_BUDGET_BYTES = 32 * 1024 * 1024

#: ``vmem_limit_bytes`` every engine kernel passes to Mosaic: the planned
#: budget plus 16 MiB for Mosaic's internal scratch and fold intermediates.
VMEM_LIMIT_BYTES = VMEM_BUDGET_BYTES + 16 * 1024 * 1024
