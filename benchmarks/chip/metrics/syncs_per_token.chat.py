"""syncs_per_token.chat: engine + scheduler.  Host syncs per token
emitted in the window: ``serve_host_syncs_total / serve_tokens_total``
(program counters)."""


def read(run):
    c = run["registry"]["counters"]
    tok = c.get("serve_tokens_total", 0.0)
    return c.get("serve_host_syncs_total", 0.0) / tok if tok else None
