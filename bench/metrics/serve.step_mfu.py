"""Share of the roofline of the ``_serve_jit`` device program over the window:
the least time the chip needs for the work the window's calls require
(the larger of operations over peak FLOP/s and bytes over peak bytes/s,
counted by ``bench/work.py``), over that program's device time in the
trace, in percent."""
import harness as H


def read(run):
    if run.trace is None:
        return None
    t = run.trace["program_s"].get("_serve_jit", 0.0)
    w = run.work.get("_serve_jit")
    if not t or not w:
        return None
    pk = H.peaks(run.device_kind)
    least = max(w["flops"] / pk["bf16_flops"],
                w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
