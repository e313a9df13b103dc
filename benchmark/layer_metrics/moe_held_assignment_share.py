"""Engine step. Of the router's assignments over the window (rows x experts a
token, every expert layer), the share that fell on an expert this chip HOLDS:
the engine's device-side histogram over the router's whole width
(`engine.expert_load()`, monitor `serving.moe.held_assignment_share`), read
when the window opens and when it closes. With random routers it is the held
share of the experts; the rest is other chips' work, and none of this one's."""


def read(rec):
    load, held = rec.get("expert_load"), rec.get("held_experts")
    if not load or not held or not load["tokens"].sum():
        return None
    first, count = held
    return float(100.0 * load["tokens"][:, first:first + count].sum()
                 / load["tokens"].sum())
