UNIT = "experts"
LAYER = "model step"
MOVES = "tpot_mid80_ms"


def read(obs):
    """Mean number of distinct experts, of those this chip HOLDS
    (n_routed_experts of the router's router_width), that a sparse layer
    reads for live rows in one decode step, over the window: the unit's
    routing counters on its access lines (_ssm.held). Also printed, and no
    metric (neither direction is better): the share of the window's
    assignments that went to experts held here, which is the share of the
    experts the file says this chip holds if routing is even."""
    import _ssm
    d = _ssm.held(obs)
    if not d:
        return None
    cfg = obs.cfg or {}
    print(f"[bench] moe.held_touched.chat: {d['moe_assignments_held']:.0f} of "
          f"{d['moe_assignments']:.0f} assignments went to experts held here "
          f"({100.0 * d['moe_assignments_held'] / max(d['moe_assignments'], 1):.1f} %; the "
          f"file holds {cfg.get('n_routed_experts')} of {cfg.get('router_width')})", flush=True)
    return d["moe_experts_touched"] / d["moe_sparse_layer_steps"]
