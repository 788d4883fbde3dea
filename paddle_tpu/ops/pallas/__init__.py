"""Pallas TPU kernels."""

#: Scoped VMEM on a v5e core is 16 MiB by default; a kernel's blocks and
#: scratch are sized to half of it.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def rows_under_budget(bytes_per_row):
    """Rows per block along a kernel's tiled row axis: the largest power
    of two, at most 256 and at least 8 (the f32 sublane tile), whose
    working set of ``bytes_per_row`` each fits the VMEM budget."""
    rows = 256
    while rows > 8 and rows * bytes_per_row > VMEM_BUDGET_BYTES:
        rows //= 2
    return rows
