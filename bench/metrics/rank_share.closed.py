"""Device executor (core/jexec.py): share of the capacity that the joins'
and compactions' rank searches searched, over the window's traced
launches: Σ `rank_rows` (the query rows searched, the live rows rounded
up to whole chunks) ÷ Σ `rank_cap_rows` (what a search over every
capacity slot would search) of the `device.launch` spans.  None where
the launches carry no such attributes."""

from bench import spans


def read(run):
    launches = [s.attrs for s in spans._spans(run.traces, "device.launch")
                if "rank_rows" in s.attrs and "rank_cap_rows" in s.attrs]
    cap = sum(a["rank_cap_rows"] for a in launches)
    if not cap:
        return None
    return sum(a["rank_rows"] for a in launches) / cap
