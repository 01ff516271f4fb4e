"""Model (`models/lm/attention._grouped_kernel`): the (query, key) pairs inside the
blocks the window layers' kernel computes over the pairs the window allows,
sum_t min(t + 1, window): the program's gauge
`swa.block_pairs_over_window_pairs`, set where the kernel is built from its
mask's block table (blocks the window does not touch are skipped). 2 at
blocks of 128 and a window of 128 (127 x 128^2 / 1,040,448 = 1.99988 at
8,192 positions), 8 at 512. A model without window layers, and a program
without the gauge, leave the metric out."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("swa.block_pairs_over_window_pairs").value
    return float(value) if value else None
