"""Least time for the aggregated coset interpolation of a cell batch, from
the live cells alone.

The count is of the arithmetic the ALGORITHM needs to turn n cells of S
field elements into the S coefficients of A(X) = sum_k r^k I_k(X), I_k the
interpolation polynomial of cell k on its coset, whatever implements it and
however its lanes are grouped or padded, in multiplications in Fr:

  per field element  its weight r^k                                     = 1
  per column present the inverse transform of the column's weighted sum
                     (cells of one column share the coset, so they are
                     summed first): a radix-2 inverse FFT of size S is
                     S/2 * log2(S) products, and the shift h^-m / S
                     another S                                          = S/2 * log2(S) + S

The program's transform is the direct S x S one (S^2 products a column, on
a multiply whose products stay in the core): that is the implementation's,
and the roofline prices the FFT.  Montgomery form, the limb layout and
empty slots are not counted.

One Fr multiplication = 6,144 int8 operations (rooflines/kzg_eval.py's
price).  Bytes: a cell's field elements (32 bytes each) and its 32-byte
weight in, the S coefficients out.
"""

from benchmarks.rooflines.kzg_eval import (
    BYTES_PER_ELEMENT,
    INT8_OPS_PER_FR_MUL,
    least_of,
)


def work(cells: int, columns: int, size: int) -> dict:
    """``cells`` live cells of ``size`` field elements in ``columns``
    distinct columns, one batch."""
    per_column = size // 2 * (size.bit_length() - 1) + size
    return {
        "ops": (cells * size + columns * per_column) * INT8_OPS_PER_FR_MUL,
        "bytes": (cells * (size + 1) + size) * BYTES_PER_ELEMENT,
    }


def cell_size(params: dict) -> int:
    ext = 2 * params["field_elements_per_blob"]
    return ext // min(128, ext)


def request_work(ctx) -> dict:
    """Every live cell of the traced window's requests; a request's
    columns are those of one block."""
    params = ctx["params"]
    per_request = work(ctx["units_per_request"],
                       params["blocks"] * params["columns"],
                       cell_size(params))
    return {k: ctx["requests"] * v for k, v in per_request.items()}


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    return least_of(request_work(ctx), peaks)
