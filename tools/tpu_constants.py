"""TPU v5e hardware constants for the analytic scaling model
(tools/scaling_model.py, SCALING.md; tests/test_telemetry.py holds the
peak to the package's table).  The compute peak is read from the
package's one table (mxnet_tpu.telemetry.PEAK_FLOPS, keyed by
device_kind, source named there); the bandwidths are this model's
assumptions."""
from mxnet_tpu.telemetry import PEAK_FLOPS

V5E_PEAK_FLOPS = PEAK_FLOPS["TPU v5 lite"]   # bf16 peak, MAC=2 convention
V5E_ICI_BW = 90e9         # B/s per chip effective all-reduce bandwidth
V5E_DCN_BW = 6.25e9       # B/s per chip (50 Gbps NIC) for cross-pod DP
