import os
import sys

import numpy as np
import pytest

# Tests run on the single real CPU device (the dry-run's 512-device flag is
# process-local to repro.launch.dryrun and must NOT leak here).
assert "xla_force_host_platform_device_count" not in \
    os.environ.get("XLA_FLAGS", "")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture
def served_tokens():
    """``served_tokens(engine, requests, eager=False)`` serves the requests
    on the engine one after another and returns, per request, the tokens it
    was served: its prefill's argmax, then each decode step's. With
    ``eager`` the engine prefills through an eager ``Model.prefill`` in
    place of its jitted program, so the two can be compared token for
    token."""
    def serve(eng, reqs, eager=False):
        if eager:
            model, dtype = eng.model, eng.dtype
            eng._prefill_program = lambda params, batch, past: \
                model.prefill(params, batch, dtype=dtype, past_cache=past)
        served = {r.req_id: [] for r in reqs}
        prefill, decode = eng._prefill, eng._decode

        def _prefill(req):
            logits, cache = prefill(req)
            served[req.req_id].append(int(np.argmax(logits[0])))
            return logits, cache

        def _decode(params, tokens, pool):
            logits, pool = decode(params, tokens, pool)
            for s, t in zip(eng.slots, np.argmax(logits, -1)):
                if s.active:
                    served[s.request.req_id].append(int(t))
            return logits, pool

        eng._prefill, eng._decode = _prefill, _decode
        for r in reqs:
            eng.submit(r)
            while eng.waiting or eng.n_active:
                eng.step()
        return [served[r.req_id] for r in reqs]

    return serve
