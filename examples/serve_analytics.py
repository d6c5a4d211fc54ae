"""End-to-end driver: semantic analytics served by REAL JAX models.

The full Nirvana pipeline — logical optimization, physical optimization,
execution — with the m1 tier backed by an actual model from the zoo running
through the continuous-batching serving engine (prefill + decode + KV cache
on this machine), in oracle-echo mode so answers stay meaningful while
latency and token accounting come from genuine serving:

    PYTHONPATH=src python examples/serve_analytics.py
"""
import jax

from repro.core import make_backends
from repro.core.dataframe import SemanticDataFrame
from repro.core.cost import DEFAULT_TIERS
from repro.data import load_dataset, WORKLOADS
from repro.configs import get_config, reduced
from repro.engine import GenerationEngine, JAXBackend
from repro.models import registry


def main():
    table, oracle = load_dataset("estate", max_rows=96)
    backends = make_backends(oracle)

    # back the m1 tier with a real served model (reduced same-family config
    # of the tier's assigned arch — qwen2-0.5b)
    tier = DEFAULT_TIERS["m1"]
    cfg = reduced(get_config(tier.arch))
    bundle = registry.build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    engine = GenerationEngine(bundle, params, max_len=192, n_slots=4)
    backends["m1"] = JAXBackend(tier, engine, oracle=oracle)
    print(f"[m1] serving {cfg.name}: {cfg.param_count()/1e6:.2f}M params, "
          f"4 slots, continuous batching")

    q = WORKLOADS["estate"][4]  # q5 (medium)
    print(f"\nQuery {q.qid}: {q.question}")
    df = SemanticDataFrame(table)
    df._ops = q.plan_for(table).ops

    report = df.execute(backends)
    print("\n=== optimized plan ===")
    print(report.plan.describe())
    res = report.result
    print("\nresult:", repr(res)[:160])
    print(f"\nreal serving stats: {engine.stats['prefills']} prefills, "
          f"{engine.stats['ticks']} decode ticks, "
          f"occupancy={engine.occupancy:.2f}")
    for tier_name, u in report.execution.meter.by_tier.items():
        print(f"  exec[{tier_name}]: calls={u.calls} "
              f"tok_in={u.tok_in:.0f} usd=${u.usd:.4f}")


if __name__ == "__main__":
    main()
