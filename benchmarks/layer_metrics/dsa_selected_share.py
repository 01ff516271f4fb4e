"""Model (`models/lm/attention.select_keys`): (query, key) pairs the indexers selected
over the causal pairs they selected from, every attention layer of the last
evaluation forward, percent: the program's gauge `dsa.selected_share`,
counted from the selections themselves. min(t + 1, index_topk) keys a query:
14,681,088 of 33,558,528 pairs a layer at 8,192 positions and 2,048 keys,
43.75. A model without an indexer, and a program without the gauge, leave
the metric out."""


def read(record, trace):
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = metrics.gauge("dsa.selected_share").value
    return float(value) if value else None
