"""How much more than an even share of a batch's votes the busiest chip
of the sharded slasher engine steps through, in percent: 100 x
(``shard_votes_max`` x ``shards`` / ``votes`` - 1), summed over the
``slasher/batch`` spans of the window's last three batches, sampled as
``harness/engine_spans.py`` samples them. The chips run one program, so
the busiest one sets its time."""

from harness import engine_spans


def read(facts):
    if not facts.get("trace"):
        return None
    batches = [r for r in engine_spans._program_roots()
               if r.name == engine_spans.BATCH and r.duration is not None
               and "shard_votes_max" in r.attrs
               and not any(sp.name == engine_spans.INIT
                           for sp in engine_spans._spans(r))]
    if len(batches) < engine_spans.SAMPLE:
        return None
    sample = batches[-engine_spans.SAMPLE:]
    votes = sum(b.attrs["votes"] for b in sample)
    busiest = sum(b.attrs["shard_votes_max"] * b.attrs["shards"]
                  for b in sample)
    return 100.0 * (busiest / votes - 1.0)
