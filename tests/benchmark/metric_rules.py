"""When two `per_layer` entries are ONE metric (ISSUE 47): their metric
files name the same reader with the same arguments, and the entries move
the same end-to-end metric in the same unit, direction and source. Such
twins are one entry with a `workloads` list, which `harness.
per_layer_metrics` reads for every cell it names; written out once a cell
they filled `per_layer` to its 128 by PR 42. And the record of PR 47's
merge: the parent's list with each entry's file (`data/per_layer_pr46.json`)
beside the table old name -> new name or "retired"
(`data/pr47_renamed.json`), through which a test written for an old name
finds today's entry."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
PER_LAYER_MAX = 128       # the contract's limit on `per_layer`
RETIRED = "retired"
# the lists a reader only sums (or multiplies): the same in any order
ORDER_FREE = {
    "benchmark.readers.engine_stat_ratio": ("over", "under"),
    "benchmark.readers.train_step_stat_ratio": ("over", "under", "less",
                                                "scale_by"),
}


def _data(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


# PR 46's `per_layer`, each entry with its metric file's content under `file`
PARENT_PER_LAYER = _data("per_layer_pr46.json")
# {old name: new name or RETIRED} for every entry PR 47 changed
RENAMED = _data("pr47_renamed.json")


def today(name):
    """Today's name of the entry PR 46 called `name`."""
    return RENAMED.get(name, name)


def metric_file(root, name):
    """`benchmark/metrics/<name>.json` under `root`, as a dict."""
    with open(os.path.join(root, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)


def reading(metric_file):
    """A metric file as what it reads: the reader and its arguments, a
    summed list sorted. Equal readings over the same evidence give the
    same value."""
    reader = metric_file["reader"]
    free = ORDER_FREE.get(reader, ())
    args = {k: sorted(v) if k in free and isinstance(v, list) else v
            for k, v in metric_file.get("args", {}).items()}
    return reader, json.dumps(args, sort_keys=True)


def twin_key(entry, metric_file):
    return (reading(metric_file), entry["moves"], entry["unit"],
            entry["better"], entry["source"])


def twins(per_layer, file_of):
    """The sets of two or more entries that are one metric; `file_of`
    gives an entry's metric file as a dict."""
    groups = {}
    for m in per_layer:
        groups.setdefault(twin_key(m, file_of(m)), []).append(m["name"])
    return [names for names in groups.values() if len(names) > 1]
