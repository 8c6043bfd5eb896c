"""core/flags.py: the typed snapshot / apply / overrides API (validated
before any value changes, exact restore), the strict bucket-list parse
behind FLAGS_serving_buckets / FLAGS_decode_buckets, and the reference's
flags that are accepted and ignored."""

import pytest

from paddle_tpu.core import flags as _flags
from paddle_tpu.core.flags import (BucketConfigError, ConfigError,
                                   UnknownFlagError)


@pytest.fixture(autouse=True)
def _restore_flags():
    snap = _flags.snapshot()
    yield
    _flags.apply(snap)


# typed snapshot / apply / overrides
# ---------------------------------------------------------------------------


class TestFlagsAPI:
    def test_snapshot_apply_roundtrip(self):
        snap = _flags.snapshot()
        prior = _flags.apply({"FLAGS_exec_steps_per_dispatch": 4,
                              "serving_max_batch_size": 16})
        assert _flags.flag("exec_steps_per_dispatch") == 4
        assert _flags.flag("serving_max_batch_size") == 16
        assert prior == {"exec_steps_per_dispatch":
                         snap["exec_steps_per_dispatch"],
                         "serving_max_batch_size":
                         snap["serving_max_batch_size"]}
        _flags.apply(prior)
        assert _flags.snapshot() == snap

    def test_unknown_flag_is_typed_and_atomic(self):
        before = _flags.flag("exec_steps_per_dispatch")
        with pytest.raises(UnknownFlagError, match="unknown flag"):
            _flags.apply({"exec_steps_per_dispatch": 8,
                          "definitely_not_a_flag": 1})
        # validation happens BEFORE any value changes: no half-applied
        # candidate config
        assert _flags.flag("exec_steps_per_dispatch") == before
        assert issubclass(UnknownFlagError, ValueError)

    def test_uncoercible_value_is_typed(self):
        with pytest.raises(ConfigError):
            _flags.apply({"exec_steps_per_dispatch": "not-an-int"})

    def test_overrides_context_restores_on_exception(self):
        before = _flags.flag("exec_steps_per_dispatch")
        with pytest.raises(RuntimeError, match="boom"):
            with _flags.overrides(exec_steps_per_dispatch=8):
                assert _flags.flag("exec_steps_per_dispatch") == 8
                raise RuntimeError("boom")
        assert _flags.flag("exec_steps_per_dispatch") == before

    def test_set_flags_stays_compatible(self):
        # the public paddle.set_flags surface keeps its ValueError
        # contract (UnknownFlagError subclasses it)
        with pytest.raises(ValueError, match="unknown flag"):
            _flags.set_flags({"FLAGS_nope": 1})


# ---------------------------------------------------------------------------
# strict bucket-list validation
# ---------------------------------------------------------------------------


class TestBucketValidation:
    def test_parse_good(self):
        assert _flags.parse_buckets("2,4,8", "t") == [2, 4, 8]
        assert _flags.parse_buckets([1, 3], "t") == [1, 3]
        assert _flags.parse_buckets("", "t") is None
        assert _flags.parse_buckets(None, "t") is None

    @pytest.mark.parametrize("bad", ["0,4", "4,2", "4,4", "-1,2", "2,x"])
    def test_parse_bad_is_typed(self, bad):
        with pytest.raises(BucketConfigError):
            _flags.parse_buckets(bad, "t")

    def test_cover(self):
        assert _flags.parse_buckets("2,8", "t", cover=8) == [2, 8]
        with pytest.raises(BucketConfigError, match="does not cover"):
            _flags.parse_buckets("2,4", "t", cover=8)
        with pytest.raises(BucketConfigError, match="end exactly"):
            _flags.parse_buckets("2,16", "t", cover=8, cover_exact=True)

    def test_serving_config_rejects_bad_flag(self):
        from paddle_tpu.serving.engine import ServingConfig

        _flags.apply({"serving_buckets": "8,4"})
        with pytest.raises(BucketConfigError):
            ServingConfig()
        _flags.apply({"serving_buckets": "0,4"})
        with pytest.raises(BucketConfigError):
            ServingConfig()
        _flags.apply({"serving_buckets": "4,8"})
        assert ServingConfig().buckets == [4, 8]
        _flags.apply({"serving_buckets": ""})
        assert ServingConfig(max_batch_size=8).buckets == [1, 2, 4, 8]

    def test_decode_config_rejects_bad_flag(self):
        from paddle_tpu.serving.decode import DecodeConfig

        _flags.apply({"decode_buckets": "4,2", "decode_max_slots": 4})
        with pytest.raises(BucketConfigError):
            DecodeConfig()
        # the set must end exactly at max_slots (fixed-step-shape
        # contract) — a ValueError subclass, like the old behavior
        with pytest.raises(ValueError):
            DecodeConfig(max_slots=4, buckets=[2, 8])
        _flags.apply({"decode_buckets": "2,4"})
        assert DecodeConfig(max_slots=4).buckets == [2, 4]
        _flags.apply({"decode_buckets": ""})
        assert DecodeConfig(max_slots=4).buckets == [4]


# ---------------------------------------------------------------------------
# search space + constraints
# ---------------------------------------------------------------------------


def test_reference_noops_are_accepted_and_unknown_names_raise():
    """A ported script's set_flags of a flag XLA makes meaningless does
    not raise, and get_flags reads the value back; a name that is in no
    table still raises the typed error."""
    assert len(_flags.ACCEPTED_AND_IGNORED) == 6
    for name, default in _flags.ACCEPTED_AND_IGNORED.items():
        assert _flags.get_flags(f"FLAGS_{name}") == {f"FLAGS_{name}": default}
    _flags.set_flags({"FLAGS_eager_delete_tensor_gb": 0,
                      "FLAGS_fraction_of_gpu_memory_to_use": 0.5,
                      "FLAGS_paddle_num_threads": 4,
                      "FLAGS_use_pinned_memory": False,
                      "FLAGS_cudnn_deterministic": True,
                      "FLAGS_max_inplace_grad_add": 8})
    assert _flags.get_flags(["paddle_num_threads", "cudnn_deterministic"]) \
        == {"paddle_num_threads": 4, "cudnn_deterministic": True}
    assert _flags.get_flags("fraction_of_gpu_memory_to_use") == \
        {"fraction_of_gpu_memory_to_use": 0.5}
    with pytest.raises(UnknownFlagError, match="unknown flag"):
        _flags.set_flags({"FLAGS_router_max_retries": 2})
    with pytest.raises(UnknownFlagError):
        _flags.get_flags("FLAGS_orch_max_restarts")
