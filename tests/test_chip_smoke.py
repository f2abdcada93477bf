"""chip_smoke.py rehearsed on the CPU at small sizes: every phase's
path, arguments and reference comparison, with the Pallas kernel in
interpret mode (the script itself refuses to run without a TPU)."""

import chip_smoke

SEED = 3


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_crush_phase():
    sizes = chip_smoke.run_phase("crush", chip_smoke.phase_crush, 2048)
    assert sizes["inputs"] == 2048 and sizes["golden_cases"] == 2


def test_osdmap_phase():
    sizes = chip_smoke.run_phase("osdmap", chip_smoke.phase_osdmap,
                                 4096, 64)
    assert sizes["pgs_with_3_up"] == 4096


def test_ec_phase():
    sizes = chip_smoke.run_phase("ec", chip_smoke.phase_ec, 4, 64 << 10,
                                 SEED)
    assert sizes["isa_k8m3"]["erased"] == [0, 2, 4]
    # off the chip every op ran the kernel in interpret mode, and the
    # engine booked it so: none counts as a device launch
    assert sizes["jerasure_k4m2"]["device_launches"] == 0
    assert sizes["jerasure_k4m2"]["interpret_launches"] >= 2


def test_served_phase():
    sizes = chip_smoke.run_phase("served", chip_smoke.phase_served, 4,
                                 64 << 10, SEED)
    assert sizes["ec_encode_ops"] >= 4
    assert sizes["interpret_launches"] == sizes["ec_encode_ops"]


def test_four_chip_phase():
    import jax

    chip_smoke.run_phase("four_chips", chip_smoke.phase_four_chips,
                         jax.devices()[:4], 4096, 4, 64 << 10, SEED)
