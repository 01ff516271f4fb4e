"""Model FLOP/s utilization of the round program: the operations that the
forward and backward passes of a round's local SGD need (3 x forward, from
the conv and dense shapes in `reference/<model>.py`) over the device seconds
of the `train+encrypt+aggregate` phase (as `train_enc_agg_dev_s` reads them)
times the chip's published bf16 peak. The phase also encrypts and
aggregates, so this is a lower bound of SGD's own share of the peak.
Percent, never clamped."""

PHASE = "hefl.phase.train+encrypt+aggregate"


def read(record, trace):
    busy = trace["phase_busy_s"].get(PHASE, 0.0) if trace else 0.0
    if busy <= 0:
        return None
    dev_s = busy / trace["rounds_traced"]
    peak = record["peaks"]["bf16_flops_per_s"]
    return 100.0 * record["train_flops_per_round"] / (dev_s * peak)
