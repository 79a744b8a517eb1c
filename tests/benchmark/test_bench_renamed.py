"""PR 47 moved no reading: every `per_layer` entry of the parent (PR 46:
`data/per_layer_pr46.json`, each entry with its metric file's content) is
found today, through the table old name -> new name
(`data/pr47_renamed.json`), as an entry that names every cell the old one
named, moves the same end-to-end metric in the same unit, direction and
source, and whose file is the old one's reader and arguments. The same
reader with the same arguments over the same evidence reads the same
value; a chip run then checks the files, it is not the proof."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import metric_rules  # noqa: E402

OLD = metric_rules.PARENT_PER_LAYER
TABLE = metric_rules.RENAMED
# the mids of a log bucket 12% wide, whose exact twins
# `backlog.prefill_dispatch_ms_per_step` and `backlog.decode_dispatch_
# ms_per_step` have stood beside them since PR 37. ISSUE 47's third kind,
# `*.pipelined_launch_share`, STAYS as one merged tripwire: a dispatch
# behind any drain point counts as not overlapped, not an engine's first
# alone (`PERF.md` section 3)
GONE = {"backlog.prefill_p50_ms", "backlog.decode_dispatch_p50_ms"}
# what waited on the room (`PERF.md` section 7 as PR 46 left it)
CAME_IN = {"mimo.prefill_attn_time_share",
           "lfm2.expert_kernel_product_share",
           "backlog.host_arrays_per_dispatch"}


def test_the_fixture_is_the_parents_whole_list():
    assert len(OLD) == metric_rules.PER_LAYER_MAX       # it was full
    assert len({m["name"] for m in OLD}) == len(OLD)
    for m in OLD:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads", "file"}
        assert m["file"]["reader"].startswith("benchmark.readers.")


def test_the_table_names_old_entries_and_todays_or_retired(spec):
    old = {m["name"] for m in OLD}
    now = {m["name"] for m in spec["per_layer"]}
    assert set(TABLE) <= old
    assert {o for o, n in TABLE.items() if n == metric_rules.RETIRED} == GONE
    for o, n in TABLE.items():
        assert o != n and o not in now
        assert n == metric_rules.RETIRED or n in now, (o, n)
    # a merged entry keeps the name of its OLDEST twin
    order = [m["name"] for m in OLD]
    for o, n in TABLE.items():
        if n != metric_rules.RETIRED:
            assert order.index(n) < order.index(o), (o, n)


def test_nothing_came_in_but_what_waited_on_the_room(spec):
    """Today's list, less what later PRs append (the arrival's entries
    here), is the parent's under today's names and the three of ISSUE 47's
    point 4: 128 -> 93."""
    carried = {metric_rules.today(m["name"]) for m in OLD} \
        - {metric_rules.RETIRED}
    assert len(carried) == 90 and not carried & CAME_IN
    first = [m["name"] for m in spec["per_layer"]][:len(carried) + 3]
    assert set(first) == carried | CAME_IN


@pytest.mark.parametrize("old", OLD, ids=lambda m: m["name"])
def test_an_entry_of_the_parent_reads_today_what_it_read(spec, spec_root,
                                                         old):
    name = metric_rules.today(old["name"])
    entries = {m["name"]: m for m in spec["per_layer"]}
    path = os.path.join(spec_root, "benchmark", "metrics",
                        old["name"] + ".json")
    if name == metric_rules.RETIRED:
        assert old["name"] not in entries and not os.path.exists(path)
        return
    now = entries[name]
    assert set(old["workloads"]) <= set(now["workloads"])
    for key in ("moves", "unit", "better", "source", "layer"):
        assert now[key] == old[key], key
    assert metric_rules.reading(metric_rules.metric_file(spec_root, name)) \
        == metric_rules.reading(old["file"])
    if name != old["name"]:           # merged away: its own file went
        assert not os.path.exists(path)
