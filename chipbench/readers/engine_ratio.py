"""Ratio of two of the engine's own counters over the window (deltas of
`Engine.stats()` between "go" and the close).  args: `over`, `under`; e.g.
tokens_generated over decode_steps = live lanes a decode step."""


def read(ctx, over, under):
    eng = (ctx.records or {}).get("engine") or {}
    if not eng.get(under):
        return None
    return eng[over] / eng[under]
