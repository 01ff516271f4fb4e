"""Model (`models/lm/experts.held_experts`): rows the grouped expert product was
given over the (token, held expert) pairs it had to compute, worst expert
layer, in the last evaluation forward: the program's gauge
`moe.rows_over_held_pairs`. A chip that holds 8 of 256 experts and gave the
product every pair would read 32; blocks of the held pairs alone read 1 to 2
(every pair is computed whatever it reads). A program without the gauge
leaves the metric out."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("moe.rows_over_held_pairs").value
    return float(value) if value else None
