"""Test harness (reference heat/core/tests/test_suites/basic_test.py:12-353).

The reference's central testing pattern is: every test is *collective* (runs identically
at any world size), ``assert_array_equal`` compares each rank's local slice against the
numpy reference, and ``assert_func_equal`` sweeps **every possible split axis** checking
the heat function against the numpy function. Both patterns are preserved; "world size"
is the device count of the mesh (1 on a single chip, N under
``--xla_force_host_platform_device_count=N``), so the same suite runs anywhere.
"""

from __future__ import annotations

import re
import unittest
from typing import Callable, Optional, Sequence, Tuple, Union

import numpy as np

import heat_tpu as ht


_LOCATION_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*\n", re.MULTILINE
)
_LOCATION_FIELDS = re.compile(r" (?:stack_frame_id|source_file|source_line)=(?:\"[^\"]*\"|\S+?)(?=[ }])")


def program_text(compiled) -> str:
    """``compiled.as_text()`` without source-location metadata (the file / function
    / location / stack-frame tables and the per-op ``stack_frame_id``): the
    byte-parity contracts are about the program, not the line the caller stands
    on. ``op_name`` scopes stay."""
    return _LOCATION_FIELDS.sub("", _LOCATION_TABLES.sub("", compiled.as_text()))


class TestCase(unittest.TestCase):
    """Base class for heat_tpu tests (reference ``basic_test.py:12``)."""

    @classmethod
    def setUpClass(cls):
        cls.comm = ht.get_comm()
        cls.device = ht.get_device()

    @property
    def world_size(self) -> int:
        return self.comm.size

    # ------------------------------------------------------------------ assertions
    def assert_array_equal(self, heat_array: ht.DNDarray, expected_array, rtol=1e-5, atol=1e-8):
        """Check global equality *and* that every device shard matches the slice the
        canonical chunk rule assigns it (reference ``basic_test.py:65-136``)."""
        self.assertIsInstance(
            heat_array, ht.DNDarray, f"The array to test was not a DNDarray, but a {type(heat_array)}"
        )
        expected_array = np.asarray(expected_array)
        self.assertEqual(
            tuple(heat_array.shape),
            tuple(expected_array.shape),
            f"global shape {heat_array.shape} != expected {expected_array.shape}",
        )
        got = heat_array.numpy()
        if expected_array.dtype.kind in "fc":
            np.testing.assert_allclose(
                np.asarray(got, dtype=expected_array.dtype), expected_array, rtol=rtol, atol=atol
            )
        else:
            np.testing.assert_array_equal(np.asarray(got), expected_array)
        # per-shard check: every device shard must hold exactly its global slice
        # (iter_shards trims the padded physical layout of ragged splits, so the
        # comparison is against the logical hyperslab)
        if heat_array.split is not None:
            for index, value in heat_array.iter_shards():
                np.testing.assert_allclose(
                    np.asarray(value).astype(
                        expected_array.dtype if expected_array.dtype.kind in "fc" else np.asarray(value).dtype
                    ),
                    expected_array[index],
                    rtol=rtol,
                    atol=atol,
                    err_msg="a device shard does not match its global slice",
                )

    def assert_func_equal(
        self,
        shape: Union[Tuple[int, ...], np.ndarray],
        heat_func: Callable,
        numpy_func: Callable,
        distributed_result: bool = True,
        heat_args: Optional[dict] = None,
        numpy_args: Optional[dict] = None,
        data_types: Sequence = (np.int32, np.float32, np.float64),
        low: int = -10000,
        high: int = 10000,
    ):
        """Test a heat function against a numpy function **for every split axis**
        (reference ``basic_test.py:138,288-299``)."""
        heat_args = heat_args or {}
        numpy_args = numpy_args or {}
        if isinstance(shape, np.ndarray):
            arrays = [shape]
        else:
            rng = np.random.default_rng(42)
            arrays = []
            for dt in data_types:
                if np.issubdtype(dt, np.integer):
                    arrays.append(rng.integers(low, high, size=shape).astype(dt))
                else:
                    arrays.append((rng.random(size=shape) * (high - low) + low).astype(dt))
        for np_array in arrays:
            expected = numpy_func(np_array, **numpy_args)
            for split in [None] + list(range(np_array.ndim)):
                ht_array = ht.array(np_array, split=split)
                result = heat_func(ht_array, **heat_args)
                if isinstance(result, ht.DNDarray):
                    self.assert_array_equal(
                        result, expected, rtol=1e-4 if np_array.dtype == np.float32 else 1e-8
                    )
                elif np.isscalar(result):
                    self.assertAlmostEqual(
                        float(result), float(expected), places=3,
                        msg=f"split={split}, dtype={np_array.dtype}",
                    )
                else:
                    np.testing.assert_allclose(np.asarray(result), expected, rtol=1e-4)
