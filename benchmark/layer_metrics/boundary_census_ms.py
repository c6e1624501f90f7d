"""Working out a pass's keys, per boundary: ``data.census_seconds``
(``ds.unique_keys()``) and ``pass.stage_seconds{stage=census}``
(``begin_pass`` sorting them again; sharded: the exchange and the split)."""
from benchmark.layer_metrics._window import histogram_sum, stage_seconds


def read(run):
    got = [histogram_sum(run, "data.census_seconds"),
           stage_seconds(run, "pass", ["census"])]
    got = [g for g in got if g is not None]
    return 1e3 * sum(got) / len(run.passes) if got else None
