"""What the readers of one scope's device time share (not a reader)."""


def device_pct(obs, scope):
    """100 x the device self time of the rows of ``obs["scopes"]``
    (``paddle.profiler.load_profiler_result``'s, runner ``train_topk_scoped``)
    whose scope has the component ``scope``, in every pass / the busy time of
    those rows; None without such rows or without the reduction."""
    scopes = obs.get("scopes")
    if not scopes or not scopes.get("busy_s"):
        return None
    rows = [r for r in scopes["rows"] if scope in r["scope"].split("/")]
    if not rows:
        return None
    return 100.0 * sum(r["seconds"] for r in rows) / scopes["busy_s"]
