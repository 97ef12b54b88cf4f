"""Device time outside the fused decode step's program, per request
admitted in the traced stretch, ms: the prefill, its page scatters and
the fork's page copies."""


def read(run):
    t = run.get("trace")
    if not t or not t["admits"]:
        return None
    return 1e3 * t["outside_decode_s"] / t["admits"]
