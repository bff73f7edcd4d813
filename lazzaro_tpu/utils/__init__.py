from lazzaro_tpu.utils.telemetry import Telemetry

__all__ = ["Telemetry"]
