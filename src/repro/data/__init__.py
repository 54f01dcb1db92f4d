from repro.data.device_cohort import (
    DeviceCohort,
    LanePlan,
    build_device_cohort,
    build_lane_plan,
)
from repro.data.pipeline import (
    ArrayDataset,
    ClientDataset,
    build_client_datasets,
    global_dataset,
    lm_token_batch,
)
from repro.data.synth_eicu import Cohort, CohortConfig, generate_cohort

__all__ = [
    "ArrayDataset",
    "ClientDataset",
    "DeviceCohort",
    "LanePlan",
    "build_client_datasets",
    "build_device_cohort",
    "build_lane_plan",
    "global_dataset",
    "lm_token_batch",
    "Cohort",
    "CohortConfig",
    "generate_cohort",
]
