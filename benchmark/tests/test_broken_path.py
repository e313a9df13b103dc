"""`correct` has to come out false when the timed path is broken underneath.
Each test breaks the program where it produces its result and drives the
rest of a run (everything but the harness's look for a chip)."""


def test_altered_token_is_refused(rehearse, monkeypatch):
    """Serving: every sampled token moved by one where it is produced, in
    the tail of the engine's compiled step (`ops/sampling.step_tail`; the
    scheduler samples nothing on the host since PR 30)."""
    from paddle_tpu.ops import sampling

    real = sampling.step_tail

    def off_by_one(logits, lanes, temperature):
        sampled = real(logits, lanes, temperature)
        return sampled.at[0].set((sampled[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(sampling, "step_tail", off_by_one)
    result, out = rehearse("mistral7b-chat-open")
    assert result["correct"] is False
    assert "served_token_widest_gap" in out and "NOT OK" in out


def test_step_that_returns_its_state_unchanged_is_refused(rehearse,
                                                           monkeypatch):
    """Training: the step computes its loss and hands back the parameters
    and moments it was given."""
    import bench

    real = bench.build_train_step

    def build(model):
        step, *state = real(model)

        def lazy(params, m, v, i, ids, labels):
            loss, *_ = step(params, m, v, i, ids, labels)
            return loss, params, m, v

        return (lazy, *state)

    monkeypatch.setattr(bench, "build_train_step", build)
    result, out = rehearse("yi6b-train-4k")
    assert result["correct"] is False
    assert "NOT OK" in out


def test_half_the_batch_left_out_is_refused(rehearse, monkeypatch):
    """Training: the loss and its gradient over the first half of the rows
    only."""
    import bench

    real = bench.build_train_step

    def build(model):
        step, *state = real(model)

        def half(params, m, v, i, ids, labels):
            n = ids.shape[1] // 2
            return step(params, m, v, i, ids[:, :n], labels[:, :n])

        return (half, *state)

    monkeypatch.setattr(bench, "build_train_step", build)
    result, _ = rehearse("yi6b-train-4k")
    assert result["correct"] is False
