"""JAX reproduction of 'Design and Implementation of an Analysis Pipeline
for Heterogeneous Data': heterogeneous pilot runtime, distributed dataframe
operators, and the model/training substrate."""
