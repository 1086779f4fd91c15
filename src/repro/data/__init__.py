from .synthetic import (DAG_SCHEMA_VERSION, SyntheticDAG, SyntheticLMData,
                        scale_estimator, synthetic_dag, synthetic_samples)

__all__ = ["DAG_SCHEMA_VERSION", "SyntheticDAG", "SyntheticLMData",
           "scale_estimator", "synthetic_dag", "synthetic_samples"]
