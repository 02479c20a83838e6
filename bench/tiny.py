"""The benchmark's cells cut to a size the CPU tests run in seconds."""

TINY_SIM = dict(n_hosts=16, hosts_per_tor=4, uplinks_per_tor=4, evs_size=256,
                queue_capacity=32, init_cwnd_pkts=16, max_cwnd_pkts=32,
                rto_ticks=200, max_msg_pkts=256)
TINY_TRAFFIC = {
    "permutation": dict(msg_pkts=48, ticks=300, chunk_ticks=100),
    "staggered": dict(n_conns=512, ticks=60, chunk_ticks=16),
}


def shrink(spec, chips=1):
    """The cell at a size the CPU runs in seconds: the same files, fabric
    and traffic kinds, fewer hosts, packets and ticks."""
    spec.config["sim"].update(TINY_SIM)
    spec.traffic.update(TINY_TRAFFIC[spec.traffic["generator"]])
    spec.chips = chips
    return spec
