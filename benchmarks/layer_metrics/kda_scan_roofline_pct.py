"""Token model (`models/lm/kda.kda_recurrence`): the time the recurrence of a
round's trained tokens must take on this chip over the time it took
(`kda_scan_dev_s`), percent, never clamped. The time it must take is the
larger of its floating-point work over the chip's bf16 peak and its bytes
over the chip's HBM bandwidth (`peaks.json`), by the model's own counts
(`reference/ling_3_flash.py`: `kda_scan_flops` 6 dk dv H a position a layer,
`kda_scan_bytes` q, k, v, g read and o written once in float32, beta read),
whatever form computes it: forward once and backward twice that (the forward
made again for the gradient is the form's own and does not count), for every
trained token (`samples_per_round` sequences of the traced length) in every
linear layer. Which of the two bounds it is in PERF.md (the bytes, at 128 x
128 a head). A program without the scope or the gauge leaves it out."""

import importlib.util
import json
import os
import re

import device_scopes as ds

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = "ling_3_flash"


def _reference():
    path = os.path.join(_BENCH, "reference", REFERENCE + ".py")
    spec = importlib.util.spec_from_file_location("_bench_ling_counts", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def configuration() -> dict:
    """The benchmark's configuration of this reference (the metric lists its
    cells alone): its published keys, and `positions` from its data set's
    name."""
    with open(os.path.join(os.path.dirname(_BENCH), "BENCHMARK.json")) as f:
        files = [c["file"] for c in json.load(f)["configs"]]
    for name in files:
        with open(os.path.join(os.path.dirname(_BENCH), name)) as f:
            conf = json.load(f)
        if conf.get("reference") == REFERENCE:
            conf["positions"] = int(re.search(
                r"-s(\d+)$", conf["experiment"]["dataset"]).group(1))
            return conf
    raise FileNotFoundError(f"no configuration of reference {REFERENCE}")


def must_take_s(conf, sequences: float, peaks) -> float:
    """Seconds the recurrence of `sequences` trained sequences must take in
    every linear layer held: forward + backward = 3 x forward."""
    ref = _reference()
    layers = sum(kind == ref.LINEAR for kind, _ in ref.layer_kinds(conf))
    work = 3 * layers * sequences * conf["positions"]
    return max(work * ref.kda_scan_flops(conf) / peaks["bf16_flops_per_s"],
               work * ref.kda_scan_bytes(conf) / peaks["hbm_bytes_per_s"])


def read(record, trace):
    took = ds.under(trace, "hefl.kda.scan", within=ds.STEP)
    if not took:
        return None
    return 100.0 * must_take_s(configuration(), record["samples_per_round"],
                               record["peaks"]) / took
