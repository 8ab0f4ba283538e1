"""merge_round_ms, and merge_round_ms.<suffix> for a merge judged apart:
total merge time of the window's rounds over their number, from
TickReport.merge_seconds (host clock, fenced on the whole merge output)."""


def read(ctx):
    rounds = [r.merge_s for r in ctx.log.in_window() if r.merge and r.merge_s is not None]
    if not rounds:
        return None
    return sum(rounds) / len(rounds) * 1e3
