"""Token model (`models/lm/`): device seconds per traced round of the custom
calls of families `splash_*`: the fused attention kernels alone
(`splash_mha_*`, `splash_mqa_fwd_*`, `splash_mqa_dq_*`, `splash_mqa_dkv_*`)."""

import device_scopes as ds


def read(record, trace):
    return ds.family(trace, "splash_")
