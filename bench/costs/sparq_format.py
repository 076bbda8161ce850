"""Bytes per cached K/V value in the SPARQ §5.1 packed format: `bits` data
bits, one MuxCtrl bit per vSPARQ pair, and a 3-bit ShiftCtrl per value
when trimming is on (the figures of the program's
`kernels/ops.py::bytes_per_value`, copied here so that a program change
cannot move the yardstick). 5opt: (4 + 0.5 + 3) / 8 = 0.9375."""


def bytes_per_value(bits: int = 4, vsparq: bool = True,
                    trimming: bool = True) -> float:
    if not trimming:
        return 1.0
    return (bits + (0.5 if vsparq else 0.0) + 3.0) / 8.0


#: the cells' cache codec, `--sparq 5opt`
KV_BYTES_PER_VALUE = bytes_per_value(4, True, True)
