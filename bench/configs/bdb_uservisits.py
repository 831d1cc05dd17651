"""Generator of the ``bdb_uservisits`` tables (UserVisits and Rankings of the
AMPLab Big Data Benchmark) from a seed, with numpy on the host, where the
engine's tables live."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from bench.datagen import draw, ints, permutation, powerlaw_ranks


def generate(cfg: Dict[str, Any], seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    n, m = int(cfg["uservisits_rows"]), int(cfg["rankings_rows"])
    uv, rk = cfg["uservisits"], cfg["rankings"]
    first = uv["visitDate_first"]
    c = draw(seed, {
        "pageURL": permutation(m),
        "pageRank": ints(1, rk["pageRank_max"] + 1, m),
        "avgDuration": ints(1, rk["avgDuration_max"] + 1, m),
        "sourceIP": ints(0, uv["sourceIP_ids"], n),
        # popularity rank -> page id: the most visited pages are random ids
        "page_of_rank": permutation(m),
        "rank": powerlaw_ranks(n, m, uv["destURL_zipf_s"]),
        "visitDate": ints(first, first + uv["visitDate_days"], n),
        "adRevenue": lambda rng: rng.random(n, dtype=np.float32),
        "userAgent": ints(0, uv["userAgent_ids"], n),
        "countryCode": ints(0, uv["countryCode_ids"], n),
        "languageCode": ints(0, uv["languageCode_ids"], n),
        "searchWord": ints(0, uv["searchWord_ids"], n),
        "duration": ints(1, uv["duration_max"] + 1, n),
    })
    rankings = {k: c.pop(k) for k in ("pageURL", "pageRank", "avgDuration")}
    page_of_rank, rank = c.pop("page_of_rank"), c.pop("rank")
    ip = c.pop("sourceIP")
    uservisits = {"sourceIP": ip, "ip7": ip >> uv["ip7_shift"], "destURL": page_of_rank[rank - 1], **c}
    return {"rankings": rankings, "uservisits": uservisits}
