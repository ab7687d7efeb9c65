"""Device-busy milliseconds per generated token over the traced window: the
union of the device-op intervals over the tokens the calls in the window
generated (prompt passes included in the time, as in ``gen_tokens_per_s``).
Beside it, on an earlier line: the bytes one decode step of the whole batch
has to read (both caches of every row and the weights) over the HBM peak."""


def read(run):
    counters = run["counters"]
    if run["trace"] is None or not counters.get("tokens"):
        return None
    family, p = run["family"], run["cell"]["params"]
    cache_bytes = 2 if p["cache_dtype"] == "bfloat16" else 4
    c, layers = family.cfg["num_channels"], family.cfg["num_self_attention_layers"]
    ca = 2 * (p["prompt_len"] + p["new_tokens"]) * c * cache_bytes
    sa = 2 * layers * (p["num_latents"] + p["new_tokens"]) * c * cache_bytes
    weights = 2 * sum(
        int(__import__("math").prod(s.shape)) for s in __import__("jax").tree.leaves(family.param_shapes(family.model()))
    )
    step_bytes = p["batch_size"] * (ca + sa) + weights
    floor_ms = 1e3 * step_bytes / run["peaks"]["hbm_bytes_per_s"] / p["batch_size"]
    value = 1e3 * run["busy_s"] / counters["tokens"]
    print(f"token_device_ms.decode: {value:.5f} ms a token on the device; reading {step_bytes / 1e6:.1f} MB a step "
          f"at the HBM peak would be {floor_ms:.5f} ms a token", flush=True)
    return value
