"""Share of the stripes' active cells that fell in the fullest key block
(device) over the measured window, in percent: 100 x the largest delta of
``merge_cells_shard_<i>`` (``merge_cells_by_shard`` one by one, the form the
harness's counter deltas keep) over their sum.  25 on four chips = the merge
work is dealt evenly; group ids are dealt in order of first sight, so a
young job's cells all lie in block 0.  Nothing where the program has no such
counters, or no stripe was flushed in the window.

Parked here, with the per-phase shares and ``stripe_padding_factor.drain``,
until a ``benchmark`` PR lets a reader that finds nothing be left out of a
traced line: the parent commit has no such counter, and its traced run of
``keyed_40m.drain.4chip`` would end with no result line (PERF.md, section 7;
the manifest entry waits in ``fixtures/mesh_entries.json``)."""

PREFIX = "merge_cells_shard_"


def read(obs):
    cells = [v for k, v in obs["counters"].items() if k.startswith(PREFIX)]
    if not cells or not sum(cells):
        return None
    return 100.0 * max(cells) / sum(cells)
