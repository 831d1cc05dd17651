# Quickstart: the paper in 80 lines, through the unified query engine.
#
# 1. A Session owns the database, the cost planner and the plan cache.
# 2. SQL and MapReduce are *frontends onto the same forelem IR*: the same
#    logical query submitted either way produces identical results and
#    shares one plan-cache entry.
# 3. The super-optimizer parallelizes (indirect partitioning §III-A1),
#    reformats the data (dictionary encoding §III-C1) and cost-picks an
#    execution method for the index sets (Fig. 1).
#
# Run:  PYTHONPATH=src python examples/quickstart.py
import numpy as np

from repro import MapReduceSpec, Session
from repro.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    # --- some web-access data (strings! the compiler will reformat) -------
    rng = np.random.default_rng(0)
    urls = np.array([f"http://site{i % 23}.com/p{i % 7}" for i in rng.integers(0, 2000, 50_000)], dtype=object)

    # --- 1. the Session front door ----------------------------------------
    s = Session(n_parts=8)
    s.register("access", url=urls)

    # --- 2. SQL through the engine (paper §IV example 1) ------------------
    r_sql = s.sql("SELECT url, COUNT(url) FROM access GROUP BY url")
    print(f"SQL: {len(r_sql.rows)} groups; top-3 by key: {sorted(r_sql.rows)[:3]}")
    print("\n=== planner EXPLAIN ===")
    print(s.explain("SELECT url, COUNT(url) FROM access GROUP BY url"))

    # --- 3. the same logical query as a MapReduce job ---------------------
    # it maps onto the same IR, flows through the same planner, and HITS
    # the plan-cache entry the SQL query created
    r_mr = s.mapreduce(MapReduceSpec.count("access", "url"))
    assert sorted(r_mr.rows) == sorted(r_sql.rows), "frontends disagree!"
    print(f"\nMapReduce execution matches SQL ✓  (plan-cache hit: {r_mr.cache_hit})")
    print("plan cache:", s.cache_stats())

    # --- the raw pipeline still exists underneath -------------------------
    # frontend → forelem IR → optimize → plan.run, plus the reference
    # interpreter as the oracle (the IR's denotational semantics)
    from repro import OptimizeOptions, optimize, sql_to_forelem
    from repro.backends import ReferenceInterpreter
    from repro.core import program_str

    prog = sql_to_forelem("SELECT url, COUNT(url) FROM access GROUP BY url", s.schemas())
    print("\n=== forelem IR (the single intermediate) ===")
    print(program_str(prog))
    res = optimize(prog, s.db, OptimizeOptions(n_parts=8))
    jax_out = sorted(res.plan.run()["R"])
    ref_out = sorted(ReferenceInterpreter(res.db).run(res.program)["R"])
    assert jax_out == ref_out == sorted(r_sql.rows)
    print("low-level pipeline and reference interpreter match ✓")


if __name__ == "__main__":
    main()
