"""InfiniteLLM-style distributed KV cluster in action: four serving
instances, one gets a burst of long-context requests, borrows rBlocks
through the gManager debt ledger, and repays on completion. Also runs the
DistAttention micro-attention merge on a multi-device host mesh.

  PYTHONPATH=src python examples/distributed_kv_cluster.py
"""

import os

# the DistAttention demo shards over a 2x4 mesh: eight virtual CPU devices
# give it that on any host
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.distkv import (GManager, RManager, dist_attention,  # noqa: E402
                               dist_attention_ref)
from repro.core.paging import BlockAllocator  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.serving.simulator import make_workload, simulate_distkv  # noqa: E402


def debt_ledger_demo():
    print("== gManager debt ledger (paper Fig. 8) ==")
    g = GManager(4)
    rms = {i: RManager(i, BlockAllocator(16, 16), g) for i in range(4)}
    for r in rms.values():
        r.register_peers(rms)

    rms[0].append_tokens(seq_id=100, new_tokens=16 * 14)  # near-full
    rms[0].append_tokens(seq_id=101, new_tokens=16 * 6)   # must borrow
    rms[3].append_tokens(seq_id=300, new_tokens=16 * 15)
    rms[3].append_tokens(seq_id=301, new_tokens=16 * 3)

    snap = g.snapshot()
    print(f"{'inst':>4} {'free/total':>12}  debtors")
    for i, row in snap.items():
        debt = ", ".join(f"inst{d} owes {b} blk" for d, b in row["debtors"])
        print(f"{i:>4} {row['free']:>5}/{row['total']:<6} {debt or '-'}")
    print(f"instance 0 seq 101 remote fraction: "
          f"{rms[0].remote_fraction(101):.0%}")
    rms[0].free_seq(101)
    print(f"after repay, ledger entries: {len(g.ledger)}")


def dist_attention_demo():
    print("\n== DistAttention: sequence-sharded micro-attention ==")
    mesh = make_mesh((2, 4), ("data", "model"))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, h, hkv, dh, s = 4, 8, 2, 64, 512
    q = jax.random.normal(ks[0], (b, h, dh))
    k = jax.random.normal(ks[1], (b, s, hkv, dh))
    v = jax.random.normal(ks[2], (b, s, hkv, dh))
    lens = jnp.array([100, 512, 7, 300], jnp.int32)
    out = dist_attention(mesh, q, k, v, lens)
    want = dist_attention_ref(q, k, v, lens)
    err = float(jnp.max(jnp.abs(out - want)))
    print(f"KV sharded over {mesh.shape['model']} model shards; "
          f"merge error vs unsharded oracle: {err:.2e}")


def cluster_sim_demo():
    print("\n== cluster simulation: borrow vs no-borrow ==")
    wl = lambda: make_workload(160, rate=12.0, dist="sharegpt", seed=1,
                               long_frac=0.08, long_len=10_000, max_len=2048)
    rd = simulate_distkv(wl(), borrow=True, blocks_per_instance=800)
    rn = simulate_distkv(wl(), borrow=False, blocks_per_instance=800)
    print(f"DistKV (borrow): {rd.throughput_tokens_per_s:6.0f} tok/s, "
          f"completed {rd.completed_frac:.0%}, preemptions {rd.preemptions}")
    print(f"local-only     : {rn.throughput_tokens_per_s:6.0f} tok/s, "
          f"completed {rn.completed_frac:.0%}, preemptions {rn.preemptions}")


if __name__ == "__main__":
    debt_ledger_demo()
    dist_attention_demo()
    cluster_sim_demo()
