from repro.core import bitmap
from repro.core.bfs_local import (BFSEngine, BFSResult, BFSRunner,
                                  LocalGraph, bfs_oracle, bfs_parent_oracle,
                                  bfs_reference,
                                  build_local_graph, count_traversed_edges,
                                  engine_num_vertices, validate_roots)
from repro.core.partition import PartitionedGraph, partition_graph
from repro.core.scheduler import (PULL, PUSH, SchedulerConfig, choose_mode,
                                  choose_mode_host)
from repro.core.vertex_program import (BFS, BFS_PARENTS, CC,
                                       INTEGRITY_MODES, PROGRAMS, SSSP,
                                       SV_CHECK, BFSParentsRunner,
                                       BudgetOverflowError,
                                       ConnectedComponentsRunner,
                                       IntegrityError, MSBFSResult,
                                       MultiSourceBFSRunner, SSSPRunner,
                                       VertexProgram, VertexProgramResult,
                                       VertexProgramRunner,
                                       check_parent_rows,
                                       component_labels, get_program,
                                       msbfs_reference, vp_reference)

__all__ = [
    "bitmap", "BFSEngine", "BFSResult", "BFSRunner", "LocalGraph",
    "MSBFSResult", "MultiSourceBFSRunner", "bfs_oracle",
    "bfs_parent_oracle", "bfs_reference",
    "build_local_graph", "count_traversed_edges", "engine_num_vertices",
    "msbfs_reference", "validate_roots", "PartitionedGraph",
    "partition_graph", "PULL", "PUSH", "SchedulerConfig", "choose_mode",
    "choose_mode_host", "BFS", "BFS_PARENTS", "CC", "SSSP", "PROGRAMS",
    "INTEGRITY_MODES", "SV_CHECK", "IntegrityError",
    "BudgetOverflowError", "VertexProgram",
    "VertexProgramResult", "VertexProgramRunner",
    "ConnectedComponentsRunner", "SSSPRunner", "BFSParentsRunner",
    "check_parent_rows", "component_labels",
    "get_program", "vp_reference",
]
