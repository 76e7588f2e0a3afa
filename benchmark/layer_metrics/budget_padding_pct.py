"""scheduler: the share of the positions the budget steps computed that
were masked padding, ``budget_padding_tokens`` / (used + padding) from
``eng.metrics()``, window only."""


def read(obs):
    m = obs.get("engine") or {}
    used = m.get("budget_tokens_used") or 0
    pad = m.get("budget_padding_tokens") or 0
    return 100.0 * pad / (used + pad) if used + pad else None
