"""Disaggregated KV over a simulated multi-node pool: the paper's §4.6
PagedAttention workload end to end, functional + timed.

A 4-node memory pool holds a paged KV cache; a compute node resolves
logical block ids through each node's Block Table with ONE Tiara
invocation per node, and the blocks stream straight back to the client
(remote-reply Memcpy).  Compare against stop-and-wait RDMA and optimally
batched RDMA.

    PYTHONPATH=src python examples/disaggregated_kv.py
"""

import numpy as np

from repro.core import costmodel as cm
from repro.core import simulator as sim
from repro.core.endpoint import TiaraEndpoint
from repro.core import operators as ops
from repro.launch.compile_cache import enable_compile_cache

N_NODES = 4
BLOCK_BYTES = 8192
BLOCKS_PER_NODE = 64
REQ_BLOCKS = 160          # the paper's LLaMA3-70B request: 160 blocks


def main() -> None:
    enable_compile_cache()
    k = ops.PagedKVFetch(n_blocks_pool=BLOCKS_PER_NODE,
                         block_bytes=BLOCK_BYTES,
                         max_req_blocks=REQ_BLOCKS)

    # devices 0..N-1 = memory nodes, device N = the compute node
    # (client); one endpoint owns the whole multi-node pool
    ep, sessions = TiaraEndpoint.for_tenants([("kv", k.regions())],
                                             n_devices=N_NODES + 1)
    sess = sessions["kv"]
    op_id = sess.register(k.build(sess.view, remote_reply=True))
    vop = ep.registry[op_id].verified
    for d in range(N_NODES):
        k.populate(sess.pool, sess.view, device=d, seed=d)

    rng = np.random.default_rng(0)
    want = rng.integers(0, N_NODES * BLOCKS_PER_NODE, REQ_BLOCKS)
    total_us = 0.0
    fetched = 0
    for node in range(N_NODES):
        ids = [int(b % BLOCKS_PER_NODE) for b in want
               if b // BLOCKS_PER_NODE == node][:REQ_BLOCKS]
        if not ids:
            continue
        k.make_request(sess.pool, sess.view, ids, device=node)
        res = sess.trace(op_id, [len(ids), N_NODES], home=node)
        assert res.status == 0 and res.ret == len(ids)
        ts = sim.simulate_task(vop, res.trace, pipelined=True,
                               serial_chain=False)
        total_us = max(total_us, ts.latency_us)   # nodes work in parallel
        fetched += len(ids)
        print(f"node {node}: {len(ids):3d} blocks in one invocation "
              f"({ts.latency_us:7.1f} us, "
              f"wire {ts.wire_bytes / 1e6:.2f} MB)")

    payload = fetched * BLOCK_BYTES
    saw = 160 * cm.DEFAULT_HW.rtt_us + payload / cm.DEFAULT_HW.wire_bytes_per_us
    batched = payload / cm.batched_rdma_gather_gbs(payload, BLOCK_BYTES) / 1e3
    print(f"\nfetched {fetched} blocks = {payload / 2**20:.1f} MiB")
    print(f"  tiara (parallel nodes, 1 invocation each): {total_us:9.1f} us")
    print(f"  stop-and-wait RDMA (as deployed, Table 1): {saw:9.1f} us")
    print(f"  optimally batched RDMA (2 RTTs + WR build): {batched:9.1f} us")
    print(f"  -> {saw / total_us:.1f}x over stop-and-wait, "
          f"{batched / total_us:.2f}x over batched")


if __name__ == "__main__":
    main()
