"""Seeded donated-grad-escape regressions: grads read after the fused
epilogue consumed them inside the step. Four sins."""
from somewhere import apply_flat_updater, fused_apply, log_norm


def plain_read_after_consume(up, flat_p, flat_g, st, it, key):
    new_p, new_s = apply_flat_updater(up, flat_p, flat_g, st, it, key)
    norm = log_norm(flat_g)                       # sin 1: direct read
    return new_p, new_s, norm


def subscript_read_after_consume(up, flat_p, g_sh, st, it, key, buckets):
    new_p_sh, new_s = apply_flat_updater(up, flat_p, g_sh, st, it, key)
    parts = [g_sh[b.key] for b in buckets]        # sin 2: bucket read
    return new_p_sh, new_s, parts


def keyword_consume_then_read(up, flat_p, grads, st, it, key):
    new_p, new_s = fused_apply(up, flat_p, state=st, iteration=it, key=key,
                               flat_grads=grads)
    tail = grads                                  # sin 3: kw-arg consume
    return new_p, new_s, tail


def branch_consume_leaks_to_tail(up, flat_p, flat_g, st, it, key, fused):
    if fused:
        new_p, new_s = apply_flat_updater(up, flat_p, flat_g, st, it, key)
    else:
        new_p, new_s = flat_p, st
    return new_p, new_s, flat_g                   # sin 4: tail after branch
