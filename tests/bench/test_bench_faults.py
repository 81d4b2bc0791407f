"""A run with the timed path broken underneath must come out not correct;
the control (the reference in float8) must fail the tiny cell's limit
while the program passes it."""
import pytest

from bench import check
from bench.families import qwen2
from benchutil import TINY_LIMITS, TINY_MODEL, run_cell


def test_a_sound_run_is_correct(tiny_root):
    rc, out = run_cell(tiny_root)
    assert rc == 0 and out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] <= \
        TINY_LIMITS["logit_gap"]["limit"]


def test_a_token_altered_where_it_is_produced(tiny_root, monkeypatch):
    from repro.serving import engine as eng

    real = eng.ServingEngine._flush

    def altered(self):
        # the packed step rows come back from the device here: bump the
        # token of every slot that decoded on an odd step
        pending = list(self._pending)
        if pending and self.stats["decode_steps"] % 2:
            import jax.numpy as jnp
            self._pending = [p.at[:, 0].set((p[:, 0] + 1)
                                            % self.cfg.vocab_size)
                             for p in pending]
            del jnp
        return real(self)

    monkeypatch.setattr(eng.ServingEngine, "_flush", altered)
    rc, out = run_cell(tiny_root)
    assert rc == 0 and not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_a_decode_step_that_returns_its_state_unchanged(tiny_root,
                                                        monkeypatch):
    from repro.serving import engine as eng

    real = eng.ServingEngine._step_fused_paged

    def stale(self):
        before = self.states
        real(self)
        self.states = before  # the KV written by this step is lost

    monkeypatch.setattr(eng.ServingEngine, "_step_fused_paged", stale)
    rc, out = run_cell(tiny_root)
    assert rc == 0 and not out["correct"]


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_the_float8_control_fails_the_limit(tiny_root, monkeypatch, seed):
    seen = {}
    real = check.served_gaps

    def spy(family, conf, s, cases, pad):
        seen["cases"] = cases
        return real(family, conf, s, cases, pad)

    monkeypatch.setattr(check, "served_gaps", spy)
    rc, out = run_cell(tiny_root, seed=seed)
    assert rc == 0 and out["correct"]
    ctrl = check.control_gaps(qwen2, {"model": TINY_MODEL}, seed,
                              seen["cases"], 512)
    assert ctrl.max() > TINY_LIMITS["logit_gap"]["limit"]
