"""Engine step. Of the positions the window's live rows could have attended
(`t + 1` summed over the rows of every `full` layer), the share their
selections hold (`|S_t|` summed): the engine's device-side counters
(`engine.selection_load()`, gauge `serving.dsa.selected_share`), read when
the window opens and when it closes. A descriptor of the traffic: 100 while no
context passes `index_topk`, `index_topk / context` far past it."""


def read(rec):
    load = rec.get("selection_load")
    if not load or not load["candidates"]:
        return None
    return float(100.0 * load["selected"] / load["candidates"])
