"""The whole MoE decode step's share of the chip's peak, in percent: model
operations of the tokens of the ticks in the traced window
(``bench/moeops.py``: latent attention, the dense layer, the shared and
the held routed experts, the router and the head) at peak, over the
host-clock time of those ticks, as ``dec.mfu`` is defined."""
import moeops


def read(run):
    steps = run.steps_in_trace()
    if not steps:
        return None
    least = sum(moeops.least_seconds(moeops.decode_token(run.config, x),
                                     run.peaks)
                for _, _, info in steps for x in info)
    return 100.0 * least / sum(b - a for a, b, _ in steps)
