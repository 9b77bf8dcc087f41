"""Each configuration's FLOP count against a count worked by hand from its
published widths."""
import json

import chipbench_tiny as tiny
from chipbench import spec


def _cell(name):
    return spec.Cell(tiny.REPO, spec.load_benchmark(tiny.REPO), name)


def test_mamba2_370m_train_flops_by_hand():
    cell = _cell("mamba2-370m.train_2k")
    # d 1024, d_inner 2048, state 128, 32 heads of 64, conv 4, chunk 256.
    per_layer = (2 * 1024 * (2 * 2048 + 2 * 128 + 32)   # in_proj
                 + 2 * 4 * (2048 + 2 * 128)             # conv
                 + 128 * 257                            # C.B, causal half
                 + 32 * 64 * 257                        # intra-chunk y
                 + 2 * 128 * 32 * 64                    # chunk states
                 + 2 * 128 * 32 * 64                    # state read-out
                 + 2 * 2048 * 1024)                     # out_proj
    assert per_layer == 14_798_976
    forward = 48 * per_layer
    head = 3 * 2 * 1024 * (1 + 1)                       # K(1+n_neg), x3
    sampler = 2 * 1024 * 32 + 1 * 16 * 2 * 32           # x_gen + tree walk
    want = 3 * forward + head + sampler
    assert want == 2_131_131_392
    got = cell.reference.train_flops_per_token(cell.config["model"],
                                               cell.config["train"]["n_neg"])
    assert got == want
    fwd = cell.reference.forward_flops_per_token(cell.config["model"])
    assert sum(fwd.values()) == forward


def test_config_file_is_the_program_config():
    """The configuration as it is run is the program's mamba2-370m."""
    from repro import configs
    from repro.models.config import ModelConfig
    cfg = json.loads((tiny.REPO / "chipbench/configs/mamba2-370m.json")
                     .read_text())
    assert ModelConfig(**cfg["model"]) == configs.get_config("mamba2-370m")
