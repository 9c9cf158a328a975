"""The scoring program's share of its roofline, in %.

Work: the Fig. 8 stream bytes of each segment scanned (one run of the
scoring program scores one segment's slab), whatever layout the device
scans; at most 2 * L flop per 4-byte word, far under the ridge, so memory
bounds it. Least time: those bytes over the chip's HBM bandwidth
(``peaks.py``). Time: the device time of the scoring program's runs in
the trace. Padding or tiles that the layout adds show as a lower share,
not as more work. Nothing to read without a device trace.
"""

PROGRAM = "jit_search"      # the scoring program's module name


def read(rec):
    red, peaks = rec.get("trace") or {}, rec.get("peaks")
    prog = (red.get("programs") or {}).get(PROGRAM)
    if not prog or not prog["runs"] or not prog["device_s"] or not peaks:
        return None
    least_s = prog["runs"] * rec["segment_stream_bytes"] / peaks[
        "hbm_bytes_per_s"]
    return 100.0 * least_s / prog["device_s"]
