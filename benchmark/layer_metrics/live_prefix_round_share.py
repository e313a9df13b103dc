"""Engine step. Of the traced window's rounds (`sched.dispatch` spans), the
share whose live rows, the span's ids `prefill_tokens + decode_lanes`, are at
most the engine's lanes: the rounds whose row-wise work an engine may run over
the live prefix of the packed buffer (`inference/live_prefix.py`). It reads
what the traffic offers, not what a program does with it: both ids are older
than the mechanism, so a program without it reads the same. A descriptor of
the cell, not a target: no change to the program moves it, and its `better`
in `BENCHMARK.json` is there because every metric carries one; a traffic mix
reshaped to raise it is another cell."""
import program_trace


def read(rec):
    pt, lanes = program_trace.of(rec), rec.get("lanes")
    if pt is None or not lanes:
        return None
    rows = [s.ids["prefill_tokens"] + s.ids["decode_lanes"]
            for s in pt.named("sched.dispatch")
            if "prefill_tokens" in s.ids and "decode_lanes" in s.ids]
    if not rows:
        return None
    narrow = sum(n <= lanes for n in rows)
    print(f"    live_prefix_round_share: {narrow} of {len(rows)} traced rounds "
          f"hold at most {lanes} live rows (the widest {max(rows)})",
          flush=True)
    return 100.0 * narrow / len(rows)
