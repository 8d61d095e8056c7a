"""The engine's host loop in the benchmark: the stable names of its
jitted steps, against the kernel patterns by which the roofline readers
find their ops."""
from bench.layers import paged_attention_roofline, ragged_prefill_roofline


def test_jitted_steps_have_stable_names():
    # the device trace names each program by its module; the roofline
    # readers find the kernels inside them by the kernels' own names
    import jax
    from repro import configs
    from repro.models import build
    from repro.serve import PagedServingEngine, Request
    model = build(configs.get_reduced("qwen3-1.7b"))
    eng = PagedServingEngine(model, model.init(jax.random.PRNGKey(0)),
                             pool_pages=40, page_size=8, max_batch=2,
                             max_len=64, prefill_chunk=8, eos_id=-1,
                             decode_path="kernel", prefill_path="kernel")
    eng.submit(Request(0, list(range(2, 8)), max_new_tokens=2))
    eng.step()
    steps = eng.lower_kernel_steps()
    assert sorted(steps) == ["decode", "prefill_64x64_1"]
    want = {"decode": ("jit_serve_decode_step",
                       paged_attention_roofline.KERNEL),
            "prefill_64x64_1": ("jit_serve_prefill_packed",
                                ragged_prefill_roofline.KERNEL)}
    for key, (module, kernel) in want.items():
        text = steps[key].compile().as_text()
        assert f"HloModule {module}," in text, key
        assert any(p in text for p in kernel), key
