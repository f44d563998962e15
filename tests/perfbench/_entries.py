"""What it means that an entry of ``BENCHMARK.json`` was appended, as
functions of the parsed file.

A cell, a configuration or a metric keeps the place it was accepted at,
and every later cell is appended after it: to ``workloads``, to
``configs``, to ``per_layer`` and to the ``workloads`` list of each metric
it reports.  So an entry test holds an entry by its index, by its order
among its neighbours, or by the cells that came before it, and never as
the last of a list: whatever a later cell appends leaves each of these
true.  ``test_perfbench_entries.py`` runs every family's entry check on a
copy of the file with such a cell appended."""


def names(entries):
    return [e["name"] for e in entries]


def entry_at(bench, kind, name, index):
    """``name`` is entry ``index`` of ``bench[kind]`` (``workloads``,
    ``configs``, ...); returns the entry."""
    assert names(bench[kind]).index(name) == index, name
    return bench[kind][index]


def metrics_in_order(bench, metric_names):
    """The per-layer metrics ``metric_names`` stand next to each other and
    in this order, wherever they are in ``per_layer``; returns their
    entries."""
    layer = names(bench["per_layer"])
    at = layer.index(metric_names[0])
    assert layer[at:at + len(metric_names)] == list(metric_names), \
        layer[at:at + len(metric_names)]
    return bench["per_layer"][at:at + len(metric_names)]


def after_earlier_cells(bench, cell, earlier):
    """In every metric's ``workloads`` that holds ``cell``, each cell of
    ``earlier`` that the list holds comes before it."""
    for m in bench["end_to_end"] + bench["per_layer"]:
        lists = m.get("workloads", ())
        if cell in lists:
            assert all(lists.index(c) < lists.index(cell)
                       for c in earlier if c in lists), m["name"]


def first_of_its_own(bench, cell, metric_names):
    """The cell is the first entry of the ``workloads`` of each metric it
    brought; later cells may follow it there."""
    for m in bench["per_layer"]:
        if m["name"] in metric_names:
            assert m["workloads"][0] == cell, m["name"]


def among_four_chip_cells(bench, cell):
    """The cell takes four chips.  How many cells may is
    ``test_perfbench_units.py``'s to hold."""
    assert cell in [w["name"] for w in bench["workloads"]
                    if w["chips"] == 4], cell


def reported(bench, cell, kind="per_layer"):
    """The names of the ``kind`` metrics the cell reports: those whose
    ``workloads`` holds it, and those without the key."""
    return {m["name"] for m in bench[kind]
            if cell in m.get("workloads", (cell,))}
