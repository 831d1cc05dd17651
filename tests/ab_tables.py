# The two-table database the differential tests share (test_join_agg,
# test_partitioned, test_paths): A (probe/fact) rows point into B
# (build/dim) through A.b_id = B.id, and the ReferenceInterpreter is the
# oracle every executor's answer is compared with.
import numpy as np

from repro.backends import ReferenceInterpreter
from repro.data.multiset import Database, Multiset

SCHEMAS = {"A": ["b_id", "f", "w"], "B": ["id", "g", "v"]}

# the core join shapes: duplicate-key equi-join, residual filter, GROUP BY
# over a two-table join with its key on either side
CORE_QUERIES = [
    # duplicate-key equi-join (fan-out > 1)
    "SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id",
    # probe-side residual filter
    "SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id AND a.w > 0",
    # GROUP BY over a two-table join, keys on either side
    "SELECT a.f, COUNT(a.f) FROM A a, B b WHERE a.b_id = b.id GROUP BY a.f",
    "SELECT a.f, SUM(b.v) FROM A a, B b WHERE a.b_id = b.id GROUP BY a.f",
    "SELECT b.g, COUNT(b.g), SUM(a.w) FROM A a, B b WHERE a.b_id = b.id GROUP BY b.g",
    "SELECT b.g, MIN(a.w), MAX(b.v) FROM A a, B b WHERE a.b_id = b.id GROUP BY b.g",
    "SELECT a.f, SUM(a.w + b.v) FROM A a, B b WHERE a.b_id = b.id GROUP BY a.f",
]


def make_db(rng, n_a=120, n_b=40, key_range=12, dup_build=True):
    """A (probe/fact) rows point into B (build/dim); dup_build repeats B
    keys so the build side has multiplicity > 1."""
    b_keys = (
        rng.integers(0, key_range, n_b).astype(np.int32)
        if dup_build
        else rng.permutation(n_b).astype(np.int32)
    )
    A = Multiset.from_columns(
        "A",
        b_id=rng.integers(0, key_range if dup_build else n_b, n_a).astype(np.int32),
        f=rng.integers(0, 6, n_a).astype(np.int32),
        w=rng.integers(-50, 50, n_a).astype(np.int32),
    )
    B = Multiset.from_columns(
        "B",
        id=b_keys,
        g=rng.integers(0, 5, n_b).astype(np.int32),
        v=rng.integers(-30, 30, n_b).astype(np.int32),
    )
    return Database().add(A).add(B)


def answer(results):
    """A run's answer in comparable form: the sorted rows of its multiset
    result ``R``, or its scalar."""
    rows = results.get("R")
    return sorted(rows) if isinstance(rows, list) else results["scalar"]


def ref_rows(p, db, params=None):
    return answer(ReferenceInterpreter(db, params).run(p))
