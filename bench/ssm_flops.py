"""Operation and byte counts of the Mamba-1 selective scan from a
configuration's shapes.

The benchmark's own arithmetic for the scan's roofline share
(``bench/metrics/ssm_scan_roofline.py``). The scan's logical work over a
sequence of ``length`` tokens, ``d_inner`` channels and ``d_state``
states, for one sequence:

* per token, channel and state: ``exp(dt A)`` (a multiply and an
  exponential), ``dA h + (dt u) B`` (two multiplies and an add) and the
  output's ``C h`` (a multiply and an add): 7 operations, with ``dt u``
  and the skip ``D u`` (a multiply and an add each) once per token and
  channel;
* bytes: u, dt, B and C read once, ``A``, ``D`` and the initial state
  ``h0`` read once, y and the final state written once, each at the dtype
  the program passes (``models/ssm.py``: u and y in the compute dtype,
  dt float32, B and C in the compute dtype, A, D and the states float32).
"""
from __future__ import annotations


def scan_flops_bytes(length: int, d_inner: int, d_state: int, *, u_bytes: int = 2,
                     dt_bytes: int = 4, bc_bytes: int = 2, y_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one sequence's selective scan."""
    ld, dn = length * d_inner, d_inner * d_state
    flops = 7.0 * ld * d_state + 3.0 * ld
    byts = ld * (u_bytes + dt_bytes + y_bytes) + 2 * length * d_state * bc_bytes
    byts += 4 * (dn + d_inner) + 2 * 4 * dn  # A and D; h0 in, h_last out
    return flops, float(byts)
