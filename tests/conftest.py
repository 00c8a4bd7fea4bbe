import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import hypothesis  # noqa: E402

# Derandomized, no-deadline profile for CI: property tests must not
# flake because a slow shared runner blew hypothesis's per-example
# deadline, and a red CI run must be reproducible locally (derandomize
# fixes the example sequence). Selected whenever CI is set (GitHub
# Actions exports CI=true); HYPOTHESIS_PROFILE overrides.
hypothesis.settings.register_profile(
    "ci",
    deadline=None,
    derandomize=True,
    max_examples=50,
)
if os.environ.get("CI"):
    hypothesis.settings.load_profile(
        os.environ.get("HYPOTHESIS_PROFILE", "ci")
    )


# ---------------------------------------------------------------------------
# shared serving-test harness
# ---------------------------------------------------------------------------
#
# test_serving.py and test_serving_prefix.py build the same smoke config,
# the same engines and the same mixed-arrival workloads; this fixture is
# the single source for that setup so new serving suites don't copy-paste
# yet another engine-construction variant. Imports stay inside methods:
# collection must not pay for (or depend on) jax.

import numpy as np  # noqa: E402
import pytest  # noqa: E402


class ServingHarness:
    """Factory for serving-engine tests: config, engine, workloads."""

    def cfg(self, arch: str = "qwen1.5-0.5b", **scaled):
        from repro.configs import smoke_config

        base = dict(
            n_layers=2,
            d_model=64,
            vocab=256,
            n_heads=4,
            n_kv_heads=4,
            head_dim=16,
            d_ff=128,
        )
        base.update(scaled)
        return smoke_config(arch).scaled(**base)

    def engine(self, quant=None, max_batch=2, max_len=64, cfg=None, **kw):
        from repro.serving import ServingEngine

        return ServingEngine(
            cfg if cfg is not None else self.cfg(),
            quant=quant,
            max_batch=max_batch,
            max_len=max_len,
            **kw,
        )

    def mixed_arrival_run(
        self, eng, n_reqs=6, arrive_every=2, seed=3, reqs=None
    ):
        """Continuous-batching traffic with MID-STREAM refills: an initial
        burst fills the slots, later requests arrive while survivors are
        mid-decode, so slots are refilled at mixed positions. Returns
        {rid: generated}."""
        from repro.serving import Request

        if reqs is None:
            rng = np.random.default_rng(seed)
            reqs = [
                Request(
                    rid=i,
                    prompt=(np.arange(3 + int(rng.integers(0, 12))) * 7 + i)
                    % 256,
                    max_tokens=3 + int(rng.integers(0, 5)),
                )
                for i in range(n_reqs)
            ]
        pending = list(reqs)
        for _ in range(min(len(pending), eng.max_batch)):
            eng.submit(pending.pop(0))
        ticks = 0
        while pending or eng.queue or any(s is not None for s in eng.slots):
            if pending and ticks % arrive_every == 0:
                eng.submit(pending.pop(0))
            eng.step()
            ticks += 1
            assert ticks < 5_000
        return {r.rid: r.generated for r in eng.finished}

    def shared_prefix_requests(
        self,
        n_clusters=3,
        per_cluster=4,
        prefix_len=24,
        suffix_lo=2,
        suffix_hi=8,
        tok_lo=3,
        tok_hi=8,
        vocab=256,
        seed=7,
    ):
        """Clustered shared-prefix workload: requests within a cluster
        share a common leading prompt (the prefix-cache hit pattern);
        suffix lengths and decode budgets vary per request."""
        from repro.serving import Request

        rng = np.random.default_rng(seed)
        reqs = []
        for c in range(n_clusters):
            prefix = rng.integers(0, vocab, size=prefix_len)
            for j in range(per_cluster):
                suffix = rng.integers(
                    0, vocab, size=int(rng.integers(suffix_lo, suffix_hi))
                )
                reqs.append(
                    Request(
                        rid=c * per_cluster + j,
                        prompt=np.concatenate([prefix, suffix]),
                        max_tokens=int(rng.integers(tok_lo, tok_hi)),
                    )
                )
        return reqs


@pytest.fixture
def serving():
    return ServingHarness()
