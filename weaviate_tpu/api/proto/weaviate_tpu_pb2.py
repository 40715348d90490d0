# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: weaviate_tpu.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x12weaviate_tpu.proto\x12\x0fweaviate_tpu.v1\"A\n\x06Vector\x12\x0e\n\x06values\x18\x01 \x03(\x02\x12\x13\n\x0btoken_bytes\x18\x02 \x01(\x0c\x12\x12\n\ntoken_dims\x18\x03 \x01(\r\"\xe6\x03\n\rSearchRequest\x12\x12\n\ncollection\x18\x01 \x01(\t\x12\x0e\n\x06tenant\x18\x02 \x01(\t\x12\r\n\x05limit\x18\x03 \x01(\r\x12\x0e\n\x06offset\x18\x04 \x01(\r\x12-\n\x0cnear_vectors\x18\x05 \x03(\x0b\x32\x17.weaviate_tpu.v1.Vector\x12\x15\n\rtarget_vector\x18\x06 \x01(\t\x12\x14\n\x0cmax_distance\x18\x07 \x01(\x02\x12\x11\n\tnear_text\x18\x08 \x01(\t\x12\x12\n\nbm25_query\x18\t \x01(\t\x12\x17\n\x0f\x62m25_properties\x18\n \x03(\t\x12\x15\n\rbm25_operator\x18\x14 \x01(\t\x12\x1a\n\x12\x62m25_minimum_match\x18\x15 \x01(\r\x12\x12\n\nuse_hybrid\x18\x0b \x01(\x08\x12\x12\n\x05\x61lpha\x18\x0c \x01(\x02H\x00\x88\x01\x01\x12\x0e\n\x06\x66usion\x18\r \x01(\t\x12\x12\n\nwhere_json\x18\x0e \x01(\t\x12\x0f\n\x07\x61utocut\x18\x0f \x01(\r\x12\x16\n\x0einclude_vector\x18\x10 \x01(\x08\x12\x14\n\x0crerank_query\x18\x11 \x01(\t\x12\x15\n\rrerank_module\x18\x12 \x01(\t\x12\x17\n\x0frerank_property\x18\x13 \x01(\tB\x08\n\x06_alpha\"|\n\tSearchHit\x12\x0c\n\x04uuid\x18\x01 \x01(\t\x12\r\n\x05score\x18\x02 \x01(\x02\x12\x10\n\x08\x64istance\x18\x03 \x01(\x02\x12\x17\n\x0fproperties_json\x18\x04 \x01(\t\x12\'\n\x06vector\x18\x05 \x01(\x0b\x32\x17.weaviate_tpu.v1.Vector\"7\n\x0bQueryResult\x12(\n\x04hits\x18\x01 \x03(\x0b\x32\x1a.weaviate_tpu.v1.SearchHit\"R\n\x0bSearchReply\x12-\n\x07results\x18\x01 \x03(\x0b\x32\x1c.weaviate_tpu.v1.QueryResult\x12\x14\n\x0ctook_seconds\x18\x02 \x01(\x02\"\x96\x02\n\x0b\x42\x61tchObject\x12\x0c\n\x04uuid\x18\x01 \x01(\t\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x0e\n\x06tenant\x18\x03 \x01(\t\x12\x17\n\x0fproperties_json\x18\x04 \x01(\t\x12\'\n\x06vector\x18\x05 \x01(\x0b\x32\x17.weaviate_tpu.v1.Vector\x12\x45\n\rnamed_vectors\x18\x06 \x03(\x0b\x32..weaviate_tpu.v1.BatchObject.NamedVectorsEntry\x1aL\n\x11NamedVectorsEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12&\n\x05value\x18\x02 \x01(\x0b\x32\x17.weaviate_tpu.v1.Vector:\x02\x38\x01\"D\n\x13\x42\x61tchObjectsRequest\x12-\n\x07objects\x18\x01 \x03(\x0b\x32\x1c.weaviate_tpu.v1.BatchObject\"\x9b\x01\n\x11\x42\x61tchObjectsReply\x12\r\n\x05uuids\x18\x01 \x03(\t\x12\x38\n\x06\x65rrors\x18\x02 \x03(\x0b\x32(.weaviate_tpu.v1.BatchObjectsReply.Error\x12\x14\n\x0ctook_seconds\x18\x03 \x01(\x02\x1a\'\n\x05\x45rror\x12\r\n\x05index\x18\x01 \x01(\r\x12\x0f\n\x07message\x18\x02 \x01(\t\"]\n\x12\x42\x61tchDeleteRequest\x12\x12\n\ncollection\x18\x01 \x01(\t\x12\x0e\n\x06tenant\x18\x02 \x01(\t\x12\x12\n\nwhere_json\x18\x03 \x01(\t\x12\x0f\n\x07\x64ry_run\x18\x04 \x01(\x08\"7\n\x10\x42\x61tchDeleteReply\x12\x0f\n\x07matches\x18\x01 \x01(\x03\x12\x12\n\nsuccessful\x18\x02 \x01(\x03\"\'\n\x11TenantsGetRequest\x12\x12\n\ncollection\x18\x01 \x01(\t\"|\n\x0fTenantsGetReply\x12\x38\n\x07tenants\x18\x01 \x03(\x0b\x32\'.weaviate_tpu.v1.TenantsGetReply.Tenant\x1a/\n\x06Tenant\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x17\n\x0f\x61\x63tivity_status\x18\x02 \x01(\t\"p\n\x10\x41ggregateRequest\x12\x12\n\ncollection\x18\x01 \x01(\t\x12\x0e\n\x06tenant\x18\x02 \x01(\t\x12\x12\n\nwhere_json\x18\x03 \x01(\t\x12\x10\n\x08group_by\x18\x04 \x01(\t\x12\x12\n\nproperties\x18\x05 \x03(\t\"%\n\x0e\x41ggregateReply\x12\x13\n\x0bresult_json\x18\x01 \x01(\tb\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'weaviate_tpu_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _BATCHOBJECT_NAMEDVECTORSENTRY._options = None
  _BATCHOBJECT_NAMEDVECTORSENTRY._serialized_options = b'8\001'
  _VECTOR._serialized_start=39
  _VECTOR._serialized_end=104
  _SEARCHREQUEST._serialized_start=107
  _SEARCHREQUEST._serialized_end=593
  _SEARCHHIT._serialized_start=595
  _SEARCHHIT._serialized_end=719
  _QUERYRESULT._serialized_start=721
  _QUERYRESULT._serialized_end=776
  _SEARCHREPLY._serialized_start=778
  _SEARCHREPLY._serialized_end=860
  _BATCHOBJECT._serialized_start=863
  _BATCHOBJECT._serialized_end=1141
  _BATCHOBJECT_NAMEDVECTORSENTRY._serialized_start=1065
  _BATCHOBJECT_NAMEDVECTORSENTRY._serialized_end=1141
  _BATCHOBJECTSREQUEST._serialized_start=1143
  _BATCHOBJECTSREQUEST._serialized_end=1211
  _BATCHOBJECTSREPLY._serialized_start=1214
  _BATCHOBJECTSREPLY._serialized_end=1369
  _BATCHOBJECTSREPLY_ERROR._serialized_start=1330
  _BATCHOBJECTSREPLY_ERROR._serialized_end=1369
  _BATCHDELETEREQUEST._serialized_start=1371
  _BATCHDELETEREQUEST._serialized_end=1464
  _BATCHDELETEREPLY._serialized_start=1466
  _BATCHDELETEREPLY._serialized_end=1521
  _TENANTSGETREQUEST._serialized_start=1523
  _TENANTSGETREQUEST._serialized_end=1562
  _TENANTSGETREPLY._serialized_start=1564
  _TENANTSGETREPLY._serialized_end=1688
  _TENANTSGETREPLY_TENANT._serialized_start=1641
  _TENANTSGETREPLY_TENANT._serialized_end=1688
  _AGGREGATEREQUEST._serialized_start=1690
  _AGGREGATEREQUEST._serialized_end=1802
  _AGGREGATEREPLY._serialized_start=1804
  _AGGREGATEREPLY._serialized_end=1841
# @@protoc_insertion_point(module_scope)
