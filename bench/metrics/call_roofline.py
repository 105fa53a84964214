"""The loop call's share of the HBM roofline: the least time its necessary
bytes take at the chip's peak bandwidth, over the device's busy time per
call. Each kind counts its own necessary bytes per call
(`bench/roofline.py`) into the counter `bytes_per_call`."""


def read(ctx):
    busy = ctx["trace"].busy_s()
    nbytes = ctx["counters"].get("bytes_per_call")
    if not busy or not nbytes:
        return None
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * ctx["units"] / busy
