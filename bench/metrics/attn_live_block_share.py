"""Share of the ragged attention kernel's (query tile × KV block) pairs
that do work, by the program's own counters: 100 · Δ``attn.blocks.live``
/ Δ``attn.blocks.all`` over the window, in percent."""
from _program import delta


def read(run):
    live, every = delta(run, "attn.blocks.live"), delta(run, "attn.blocks.all")
    return 100.0 * live / every if live is not None and every else None
