"""The least time one step needs on this device, from shapes
(benchmark/costs.py and the model's ``step_cost``; the larger of flops
over the matmul peak and bytes over the HBM peak), over the device time a
step took in the trace, in %."""

from benchmark import costs


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    least, _ = costs.roofline_seconds(
        run.step_cost(), costs.load_peaks(run.device_kind))
    return 100.0 * least * run.traced_steps / run.trace["step_busy_s"]
