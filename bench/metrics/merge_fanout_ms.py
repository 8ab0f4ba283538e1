"""merge_fanout_ms: host time a paged merge round spends writing the
merged models back into the arena, in ms: the round's summed
``merge.fanout`` spans, averaged over the window's rounds (program
spans, host clock)."""
from bench import spans


def read(ctx):
    got = spans.window(ctx, "merge.gather", "merge.solve", "merge.fanout")
    rounds = spans.by_seq(s for s in got if s.name == "merge.fanout")
    if not rounds:
        return None
    spans.note(ctx, "merge_fanout_ms", got)
    per_round = [sum(s.seconds for s in ss) for ss in rounds.values()]
    return sum(per_round) / len(per_round) * 1e3
