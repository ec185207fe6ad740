"""Share of the HBM roofline the serve programs reach: the bytes the
timing model needs per simulated request, times the requests served,
over (serve device time x the chip's peak HBM bandwidth).

Per request the model reads its issue cycle and its metadata word
(bank, row-buffer kind, validity) and writes its finish cycle: three
int32 values, whatever layout the program chooses.  Bound by bytes:
the serve step does no arithmetic worth a compute roof."""

BYTES_PER_REQUEST = 3 * 4


def read(ctx):
    tr, w = ctx["trace"], ctx["window"]
    if not tr or not tr["layer_programs"]["serve"] or not w["requests"]:
        return None
    seconds = tr["layer_s"]["serve"]
    if seconds <= 0:
        return None
    need = w["requests"] * BYTES_PER_REQUEST / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / seconds
