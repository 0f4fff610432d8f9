from repro.roofline.analysis import state_traffic_bytes, vmem_step_bytes

__all__ = ["state_traffic_bytes", "vmem_step_bytes"]
