"""Engine step. Tokens routed to the busiest expert of any expert layer over
the tokens routed to the mean expert, over the window: the engine's
device-side histogram (`engine.expert_load()`, monitor
`serving.moe.expert_tokens`), read when the window opens and when it closes.
1 is a perfectly even router; a grouped matmul's time follows the experts
touched, a sharded expert layer's would follow this."""


def read(rec):
    load = rec.get("expert_load")
    if not load:
        return None
    moe = load["tokens"][load["tokens"].sum(axis=1) > 0]
    return float(moe.max() / moe.mean()) if moe.size else None
