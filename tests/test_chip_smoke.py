"""The chip smoke's phases, end to end at the smoke config on the CPU.

``chip_smoke.py`` drives the full-width model on a TPU; these tests run
the same phase functions at ``get_smoke_config("llama3.2-1b")`` with the
Pallas kernels interpreted, and check that ``main()`` refuses the CPU.
"""
import os
import sys

import pytest

from repro.configs import get_smoke_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config(chip_smoke.ARCH)
    base, tenants = chip_smoke.build(cfg, seed=0)
    stream = chip_smoke.make_stream(cfg, [n for n, _, _ in tenants], seed=0)
    return cfg, base, tenants, stream


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert isinstance(e.value.code, str) and "'cpu'" in e.value.code
    assert '"ok"' not in capsys.readouterr().out


def test_stream_covers_every_tenant_and_the_base(smoke_model):
    _, _, tenants, stream = smoke_model
    owners = [who for who, _ in stream]
    assert owners.count(None) == 1
    assert {n for n, _, _ in tenants} <= set(owners)
    assert {len(p) for _, p in stream} == {chip_smoke.PROMPT_LEN}


def test_kernel_phase_interpreted():
    out = chip_smoke.kernel_phase(get_smoke_config(chip_smoke.ARCH),
                                  interpret=True)
    assert set(out) == {"delta_spmm", "fused_base_delta",
                        "delta_spmm_segments", "dequant"}
    for name, r in out.items():
        assert r["err"] <= r["tol"], name


def test_serving_and_correctness_phases(smoke_model):
    cfg, base, tenants, stream = smoke_model
    eng, reqs = chip_smoke.serving_phase(cfg, base, tenants, stream)
    assert set(eng.metrics.report()["decode_paths"]) == \
        {"segments-xla-select+packed"}
    ref, ident = chip_smoke.identity_phase(cfg, base, tenants, stream, reqs)
    # the CPU runs both engines' arithmetic identically: exact identity
    assert ident == {"exact": len(reqs), "diverged": []}
    out = chip_smoke.logits_phase(cfg, ref, tenants, stream)
    assert set(out) == {n for n, _, _ in tenants}
    for r in out.values():
        assert r["prefill_err"] <= chip_smoke.LOGIT_TOL
        assert r["decode_err"] <= chip_smoke.LOGIT_TOL
        assert r["shift_vs_base"] >= chip_smoke.TENANT_MIN_SHIFT


def test_mesh_phase_on_four_devices(subproc):
    out = subproc(f"""
    import sys
    sys.path.insert(0, {REPO!r})
    import chip_smoke
    from repro.configs import get_smoke_config
    cfg = get_smoke_config(chip_smoke.ARCH)
    base, tenants = chip_smoke.build(cfg)
    stream = chip_smoke.make_stream(cfg, [n for n, _, _ in tenants])
    shapes = chip_smoke.mesh_phase(cfg, base, tenants, stream, n_devices=4)
    assert shapes == [{{'data': 1, 'model': 4}}, {{'data': 2, 'model': 2}}]
    print('OK')
    """, n_devices=4)
    assert "OK" in out


def test_check_tokens_refuses_a_token_far_from_the_best(smoke_model):
    cfg, base, tenants, stream = smoke_model
    ref = chip_smoke.reference_engine(cfg, base, tenants)
    who, prompt = stream[0]
    want = ref.generate(who, prompt[None], max_new_tokens=2)[0]
    worst = int(chip_smoke._next_logits(ref, who, prompt, [want[0]]).argmin())
    got = want.copy()
    got[1] = worst
    assert chip_smoke.check_tokens(ref, [(who, prompt)], [want], [want],
                                   "same") == {"exact": 1, "diverged": []}
    with pytest.raises(chip_smoke.SmokeFailure, match="not a near-tie"):
        chip_smoke.check_tokens(ref, [(who, prompt)], [got], [want], "far")
