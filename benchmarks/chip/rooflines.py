"""What the algorithm needs from its shapes, and the chip's published peaks.

The work counted here is the algorithm's (one read of the operand per Lloyd
iteration; 2n^3 per matmul), not what a kernel happens to do, so a share read
against it survives a PR that replaces the kernel.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(f"no peaks on record for device_kind {device_kind!r}; "
                         "add it to benchmarks/chip/peaks.json with its source")
    return table[device_kind]


def kmeans_fit_floor_s(config: dict, peak: dict, chips: int) -> float:
    """One read of the float32 operand per Lloyd iteration, at the HBM peak."""
    nbytes = config["max_iter"] * config["rows"] * config["features"] * 4
    return nbytes / (chips * peak["hbm_bytes_per_s"])


def matmul_chain_floor_s(config: dict, peak: dict, chips: int) -> float:
    """``chain`` dependent n x n x n products, at the bf16 MXU peak of all chips."""
    flops = config["chain"] * 2 * config["n"] ** 3
    return flops / (chips * peak["bf16_flops_per_s"])
