"""kernels: of the tiles the flash kernels' grids have, the share they
compute (the others hold no allowed entry of the mask and are skipped whole):
100 x ``paddle_flash_tiles_visited_total`` / ``paddle_flash_tiles_total``,
counted when each kernel is traced, from its static grid. The block-diffusion
rule at 8,192 tokens and 1024-wide tiles visits 80 of 256 tiles a head, 31.25;
a causal mask a little over half; no mask all. Nothing from a program without
the counters (before PR 32) or that traced no flash kernel."""


def read(obs):
    from paddle_tpu.inference import telemetry
    visited, total = (
        telemetry.runtime_counter(f"paddle_flash_tiles_{which}total", 0)
        for which in ("visited_", ""))
    return 100.0 * visited / total if total else None
