"""Device seconds per partition of the fixpoint's programs: the fold
(``fold_*``), the lifting tables and the compaction (``compact_actives``,
``count_live_distinct``), summed by program name from the trace."""

import re

PROGRAMS = re.compile(r"fold|lift_tables|compact_actives|count_live")


def read(layer):
    tr, parts = layer.get("trace"), layer.get("partitions")
    if not tr or not parts:
        return None
    s = sum(v for k, v in tr["programs"].items() if PROGRAMS.search(k))
    return s / len(parts) if s > 0 else None
