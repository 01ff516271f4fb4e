"""HE kernels (`ckks/pallas_ntt`): device seconds per round of the ops that
carry a `hefl.*` scope in their own name. On the chip those are the Pallas
custom calls (`%hefl.encrypt.N`, `%hefl.decrypt.N`, ...); the XLA ops around
them (encode, sampling, the modular sum) carry no scope in the trace."""

SCOPES = ("hefl.encrypt", "hefl.psum_aggregate", "hefl.decrypt",
          "hefl.transcipher")


def read(record, trace):
    if not trace:
        return None
    total = sum(trace["scope_s"].get(s, 0.0) for s in SCOPES)
    return total / trace["rounds_traced"] if total > 0 else None
