"""A check added as a file: a round's work and one planted number, both read
from the configuration's file and the seed, no model and no reference."""


def round_work(cell, cfg, data):
    return {"samples_per_round": cell["config"]["planted"]["samples_per_round"],
            "train_flops_per_round": 1.0}


def numbers(cell, cfg, data):
    got = {"planted": float(cfg.seed % 100)}
    if cfg.seed % 100 == 99:  # a number the configuration gives no limit
        got["unlimited"] = 0.0
    return got
