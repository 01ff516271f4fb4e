"""Token model (`models/lm/kda._kda_front`): the time the front of a round's
linear layers must take on this chip over the time its kernel pair took
(`kda_front_kernel_dev_s`), percent, never clamped. A pass of a sequence
through a layer's front must take the larger of its floating-point work over
the chip's bf16 peak and its bytes over the chip's HBM bandwidth
(`peaks.json`), by the model's own counts (`reference/ling_3_flash.py`:
`kda_front_flops`, `kda_front_bytes`, a position a layer a pass). The passes
a round are the model's own too, in every linear layer: a trained sequence
goes forward, forward again for the gradient (the layer's checkpoint) and
backward, a validation and an evaluation sequence forward once; the kernels'
seconds are read wherever they ran, so all of them count. Which bound holds
is in PERF.md (the bytes). A program without the kernels leaves it out."""

import functools

import device_scopes as ds
from layer_metrics import kda_scan_roofline_pct as scan

_reference = functools.lru_cache(maxsize=1)(scan._reference)  # loaded once


def passes(conf, trained: float) -> tuple[float, float]:
    """(forward, backward) passes of one sequence through one layer's front
    in a round that trains `trained` sequences, by the configuration's own
    experiment: a shard's `val_fraction` is validated once an epoch beside
    what is trained, `n_test` sequences are evaluated."""
    ref, exp = _reference(), conf["experiment"]
    layers = sum(kind == ref.LINEAR for kind, _ in ref.layer_kinds(conf))
    share = exp["train"]["val_fraction"]
    validated = trained * share / (1.0 - share)
    return (layers * (2 * trained + validated + exp["n_test"]),
            layers * trained)


def must_take_s(conf, trained: float, peaks) -> float:
    """Seconds the front's passes of a round must take."""
    ref = _reference()
    return sum(
        n * conf["positions"] * max(
            ref.kda_front_flops(conf, back) / peaks["bf16_flops_per_s"],
            ref.kda_front_bytes(conf, back) / peaks["hbm_bytes_per_s"])
        for n, back in zip(passes(conf, trained), (False, True)))


def read(record, trace):
    took = ds.family(trace, "kda_front_")
    if not took:
        return None
    return 100.0 * must_take_s(scan.configuration(),
                               record["samples_per_round"],
                               record["peaks"]) / took
