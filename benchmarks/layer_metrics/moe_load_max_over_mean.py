"""Model (`models/lm/experts.held_experts`): the busiest held expert's (token,
expert) pairs over the mean of the held experts, worst expert layer, in the
last evaluation forward: the program's gauge `moe.load_max_over_mean`. 1 is
a balanced router; every pair is computed whatever it reads."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("moe.load_max_over_mean").value
    return float(value) if value else None
