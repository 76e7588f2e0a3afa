"""trainer: of the data tokens a block-diffusion model has seen, the share
its noise replaced by the mask id: 100 x ``paddle_sdar_masked_tokens_total``
/ ``paddle_sdar_tokens_total`` (``paddle_tpu.models.sdar.noise_stats()``:
counts the model keeps ON THE DEVICE as state of the step, one transfer when
read, since the model was built). The linear schedule ``t = eps + (1 - eps)
u`` masks ``(1 + eps) / 2`` of them, 52.5 at ``eps`` 0.05; only masked tokens
carry loss. Nothing from a program that has no such model (before PR 32) or
built none."""


def read(obs):
    try:
        from paddle_tpu.models import sdar
    except ImportError:
        return None
    st = sdar.noise_stats()
    return 100.0 * st["masked"] / st["tokens"] if st["tokens"] else None
