"""A toy architecture for the harness's tests: the dense decoder of
``bench/arch/dense.py`` with its layers alternating a sliding window of
``sliding_window`` positions and global attention, which the program
serves as one "pattern" stage (``pattern=(ATTN_LOCAL, ATTN_GLOBAL)``).
Layer ``r`` is position ``r % 2`` of the period, repeat ``r // 2``: its
weights are entry ``r // 2`` of the leaves under ``stages/0/<r % 2>/``.
"""
import dataclasses

import spec

dense = spec.arch({"arch": "dense"})
PERIOD = 2


def sizes(name, conf):
    m = dense.sizes(name, conf)
    if not m.window or m.n_layers % PERIOD:
        raise ValueError(f"{name}: needs a window and whole periods")
    return m


def _global(m):
    return dataclasses.replace(m, window=0)


def program(m):
    from repro.configs.base import ATTN_GLOBAL, ATTN_LOCAL
    return dataclasses.replace(dense.program(m),
                               pattern=(ATTN_LOCAL, ATTN_GLOBAL))


def layout(m):
    per = dense.layout(dataclasses.replace(m, n_layers=m.n_layers // PERIOD))
    out = {}
    for path, (shape, scale, stacked) in per.items():
        if not stacked:
            out[path] = (shape, scale, stacked)
            continue
        for pos in range(PERIOD):
            out[path.replace("stages/0/", f"stages/0/{pos}/", 1)] = \
                (shape, scale, stacked)
    return out


def layer_at(m, r):
    return f"stages/0/{r % PERIOD}/", r // PERIOD


def layer(m, r, p, x, quant):
    return dense.layer(m if r % PERIOD == 0 else _global(m), r, p, x, quant)


logits = dense.logits
embed = dense.embed


def decode_work(m, ctxs):
    """Half the layers windowed, half global."""
    b, f = dense.decode_work(m, ctxs)
    gb, gf = dense.decode_work(_global(m), ctxs)
    return (b + gb) // 2, (f + gf) / 2


def prefill_flops(m, S):
    return (dense.prefill_flops(m, S) + dense.prefill_flops(_global(m), S)) / 2
