"""Model FLOP/s utilisation of training: the operations the window's
steps require (``bench/work.py``: encoder once per distinct node,
aggregator once per endpoint, contrastive once per edge and direction,
RQ distances once per endpoint occurrence; backward counted) over the
window's wall time, as a share of the chip's bf16 peak, in percent."""
import harness as H


def read(run):
    w = run.work.get("train_step")
    if not w or not run.window_s or not run.traced:
        return None
    pk = H.peaks(run.device_kind)
    return 100.0 * w["flops"] / run.window_s / pk["bf16_flops"]
