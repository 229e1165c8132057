"""extract_roofline — kernels: bytes the _extract* calls had to move (from their shapes: rows x L in,
4 x rows lengths, rows x captures x 8 out) over the chip's HBM peak, as a
percentage of their device time.  Bound: hbm."""

from benchlib import observe


def read(obs):
    return observe.extract_roofline(obs)
